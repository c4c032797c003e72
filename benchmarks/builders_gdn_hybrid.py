"""Builder of the Gated-DeltaNet hybrid family (the ``olmo_hybrid`` block:
linear-attention layers with a matrix state a head beside full-attention
layers) for ``builders.build``: named by a configuration file's
``"builder": "benchmarks.builders_gdn_hybrid:build"``.

The same constructors a caller of the library uses
(``GdnHybridConfig.from_hf`` on the file's published keys, ``init_params``
on the device from the seed, ``GdnHybridGenerator``, ``ServeEngine``).
Every key of the file's ``engine`` group reaches a constructor here; the
state group's slots are not among them — the engine derives them.
"""

from __future__ import annotations

import numpy as np


def hf_keys(config: dict) -> dict:
    """The model's own keys of the file: every key ``from_hf`` knows.  What
    the file holds beside them is the benchmark's (source, reduced,
    deployment, assumed, engine, ..)."""
    from triton_dist_tpu.models.gdn_hybrid import HF_KEYS

    return {k: v for k, v in config.items() if k in HF_KEYS}


def model_config(config: dict):
    import jax.numpy as jnp

    from triton_dist_tpu.models.gdn_hybrid import GdnHybridConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return GdnHybridConfig.from_hf(
        hf_keys(config), max_seq=config["engine"]["max_seq"],
        dtype=dtypes[config["torch_dtype"]])


def weight_key(seed: int):
    """The recipe's key, kept with the reference."""
    from benchmarks.reference.gdn_hybrid import weight_key as key

    return key(seed)


def build(config: dict, seed: int, *, chips: int, ladder: list,
          interpret: bool = False):
    """-> (engine, GdnHybridConfig).  Weights first, pools second."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import gdn_hybrid
    from triton_dist_tpu.serve import ServeEngine

    if chips != 1:
        raise ValueError("this builder places one stage on one chip")
    cfg = model_config(config)
    eng = dict(config["engine"])
    kv_dtype = {"bfloat16": None, "int8": jnp.int8}[eng.pop("kv_dtype")]
    max_seq = eng.pop("max_seq")
    params = gdn_hybrid.init_params(cfg, weight_key(seed))
    jax.block_until_ready(params)
    gen = gdn_hybrid.GdnHybridGenerator(
        cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)), axis="sp",
        max_seq=max_seq, interpret=interpret, kv_dtype=kv_dtype)
    engine = ServeEngine(gen, params, bucket_ladder=ladder, **eng)
    return engine, cfg
