"""The program's ``TextLoader``: each passage's text by its docno."""

ROLE = "loader"
CORPUS = True


def build(world, name, spec):
    from repro.ir import TextLoader
    return TextLoader(dict(zip(world.corpus.docnos, world.texts)))


def warm(world, stage, spec, queries):
    """Nothing compiles: a dictionary lookup on the host."""
