"""What the stage kinds that run the program's BERT-shaped encoder block
(``bench/kinds/mono.py``, ``duo.py``, ``dense.py``) share: the widths,
read from the configuration's top-level keys under BERT's names; the
program's ``EncoderConfig``; the weights of a stream of ``weight_seed``;
the token layout from word ids; and the work of encoder passes."""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import flops, gen, weights
from .reference.tokens import Tokens


def widths(cfg: Dict) -> Dict:
    return {"L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "S": int(cfg["max_len"])}


def encoder_config(cfg: Dict, stage: str):
    """The program's ``EncoderConfig`` of stage ``stage``."""
    from repro.models.cross_encoder import EncoderConfig
    w = widths(cfg)
    return EncoderConfig(name=f"{cfg['name']}.{stage}", n_layers=w["L"],
                         d_model=w["d"], n_heads=w["H"], d_ff=w["F"],
                         vocab_size=w["V"], max_len=w["S"])


def params(cfg: Dict, stream: int):
    """The encoder's weights from stream ``stream`` of ``weight_seed``."""
    return weights.encoder_params(
        cfg, cfg["max_len"], gen.sub_seed(cfg["weight_seed"], stream))


def tokens(cfg: Dict, inputs) -> Tokens:
    """The program's token layout over the queries' vocabulary."""
    return Tokens(inputs.word_hash, widths(cfg)["V"],
                  gen.fnv1a32_words(["vs"])[0])


def work(cfg: Dict, real_tokens: np.ndarray, score_head: bool = True
         ) -> Dict:
    """Operations of passes over ``real_tokens`` and the layer weights
    one call reads."""
    w = widths(cfg)
    return {"encoder_flops": flops.encoder_flops(
                real_tokens, w["L"], w["d"], w["F"], score_head=score_head),
            "encoder_weight_bytes": flops.encoder_weight_bytes(
                w["L"], w["d"], w["F"])}
