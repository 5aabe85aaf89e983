"""The DAN family (deep averaging network): its seeded weights, its plain
reference scorer, the work the algorithm needs, and the one function that
hands the weights to the program. Everything but ``to_program`` is numpy
only."""

from __future__ import annotations

import numpy as np

import fixtures
from reference import QUANTIZE

MOTIFS = ("left_motif", "right_motif")


def numeric_features() -> list[str]:
    return [f for f in fixtures.RUN_FEATURES if f not in MOTIFS]


def arrays(weights_seed: int, config: dict) -> dict:
    """float32 parameters; numeric columns are normalised by the middle and
    half-width of their range, so every input moves the logit."""
    numeric = numeric_features()
    embed_dim, hidden, n_layers = config["embed_dim"], config["hidden"], config["n_layers"]
    rng = np.random.default_rng(weights_seed)
    in_dim = len(numeric) + 2 * embed_dim
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    params = {
        "motif_embed": f32(rng.normal(size=(fixtures.MOTIF_VOCAB, embed_dim)) * 0.5),
        "w_in": f32(rng.normal(size=(in_dim, hidden)) / np.sqrt(in_dim)),
        "b_in": f32(rng.normal(size=hidden) * 0.1),
        "w_out": f32(rng.normal(size=(hidden, 1)) * (4.0 / np.sqrt(hidden))),
        "b_out": f32(np.zeros(1)),
    }
    for i in range(n_layers - 1):
        params[f"w_{i}"] = f32(rng.normal(size=(hidden, hidden)) / np.sqrt(hidden))
        params[f"b_{i}"] = f32(rng.normal(size=hidden) * 0.1)
    lo = np.array([fixtures.FEATURE_RANGE[f][0] for f in numeric])
    hi = np.array([fixtures.FEATURE_RANGE[f][1] for f in numeric])
    return {"params": params, "numeric_features": numeric,
            "norm_mu": f32((lo + hi) / 2), "norm_sd": f32(np.maximum((hi - lo) / 2, 0.5)),
            "embed_dim": embed_dim, "hidden": hidden, "n_layers": n_layers}


def to_program(config: dict, w: dict):
    """The program's model object over the benchmark's arrays (the only
    import of the program in this file)."""
    from variantcalling_tpu.models import dan as dan_mod

    cfg = dan_mod.DanConfig(n_numeric=len(w["numeric_features"]),
                            embed_dim=config["embed_dim"], hidden=config["hidden"],
                            n_layers=config["n_layers"])
    return dan_mod.DanModel(cfg=cfg, params_np=w["params"],
                            feature_names=list(fixtures.RUN_FEATURES),
                            numeric_features=w["numeric_features"],
                            norm_mu=w["norm_mu"], norm_sd=w["norm_sd"])


def _gelu(x):
    return (0.5 * x * (1.0 + np.tanh(np.float32(np.sqrt(2.0 / np.pi))
                                     * (x + np.float32(0.044715) * x * x * x)))).astype(np.float32)


def score(da: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """sigmoid(MLP([normalised numerics, embed(left motif), embed(right
    motif)])) with tanh-GELU hidden layers; products accumulate in float32."""
    q = QUANTIZE[precision]
    p = da["params"]
    idx = {f: i for i, f in enumerate(fixtures.RUN_FEATURES)}
    numeric = (x[:, [idx[f] for f in da["numeric_features"]]] - da["norm_mu"]) \
        / np.maximum(da["norm_sd"], 1e-6)
    motif = lambda name: p["motif_embed"][  # noqa: E731
        np.clip(x[:, idx[name]].astype(np.int64), 0, fixtures.MOTIF_VOCAB - 1)]
    h = np.concatenate([numeric, motif(MOTIFS[0]), motif(MOTIFS[1])],
                       axis=1).astype(np.float32)
    h = q(_gelu(q(q(h) @ q(p["w_in"]) + p["b_in"])))
    for i in range(da["n_layers"] - 1):
        h = q(_gelu(q(q(h) @ q(p[f"w_{i}"]) + p[f"b_{i}"])))
    logit = q((q(h) @ q(p["w_out"]))[:, 0] + p["b_out"][0])
    return (1.0 / (1.0 + np.exp(-logit.astype(np.float64)))).astype(np.float32)


# -- required work, from the configuration's shapes alone ---------------------

def _in_dim(config: dict) -> int:
    return config["n_numeric"] + 2 * config["embed_dim"]


def flops_per_variant(config: dict) -> float:
    h = config["hidden"]
    return 2.0 * (_in_dim(config) * h + (config["n_layers"] - 1) * h * h + h)


def bytes_per_variant(config: dict) -> float:
    """Feature row in (numerics and two motif codes), score out, float32."""
    return 4.0 * (config["n_numeric"] + 2) + 4.0


def table_bytes(config: dict) -> float:
    """The model's tables, read once per call."""
    h = config["hidden"]
    return 4.0 * (_in_dim(config) * h + (config["n_layers"] - 1) * h * h + 2 * h
                  + fixtures.MOTIF_VOCAB * config["embed_dim"])
