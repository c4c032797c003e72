"""Builder of the latent-attention + expert-share family (DeepSeek-V3
block) for ``builders.build``: named by a configuration file's
``"builder": "benchmarks.builders_mla_moe:build"``.

The same constructors a caller of the library uses
(``MlaMoeConfig.from_hf`` on the file's published keys, ``init_params`` on
the device from the seed, ``MlaMoeGenerator``, ``ServeEngine``).  Every key
of the file's ``engine`` group reaches a constructor here.
"""

from __future__ import annotations

import numpy as np


def model_config(config: dict):
    import jax.numpy as jnp

    from triton_dist_tpu.models.mla_moe import MlaMoeConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config["share"]
    return MlaMoeConfig.from_hf(
        config, max_seq=config["engine"]["max_seq"],
        dtype=dtypes[config["torch_dtype"]],
        experts_total=share["experts_total"],
        expert_offset=share["expert_offset"])


def weight_key(seed: int):
    """The recipe's key, kept with the reference."""
    from benchmarks.reference.mla_moe_share import weight_key as key

    return key(seed)


def build(config: dict, seed: int, *, chips: int, ladder: list,
          interpret: bool = False):
    """-> (engine, MlaMoeConfig).  Weights first, pools second."""
    import jax
    from jax.sharding import Mesh

    from triton_dist_tpu.models import mla_moe
    from triton_dist_tpu.serve import ServeEngine

    if chips != 1:
        raise ValueError("this builder places one chip's share of the "
                         "deployment on one chip")
    cfg = model_config(config)
    eng = dict(config["engine"])
    kv_dtype = {"bfloat16": None, "int8": "int8"}[eng.pop("kv_dtype")]
    max_seq = eng.pop("max_seq")
    params = mla_moe.init_params(cfg, weight_key(seed))
    jax.block_until_ready(params)
    gen = mla_moe.MlaMoeGenerator(
        cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)), axis="sp",
        max_seq=max_seq, interpret=interpret, kv_dtype=kv_dtype)
    engine = ServeEngine(gen, params, bucket_ladder=ladder, **eng)
    return engine, cfg
