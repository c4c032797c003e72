"""Builder of the window + full attention, softmax-routed expert family
(the ``mellum`` block) for ``builders.build``: named by a configuration
file's ``"builder": "benchmarks.builders_swa_moe:build"``.

The same constructors a caller of the library uses
(``SwaMoeConfig.from_hf`` on the file's published keys, ``init_params`` on
the device from the seed, ``SwaMoeGenerator``, ``ServeEngine``).  Every key
of the file's ``engine`` group reaches a constructor here; the window
group's block count is not among them — the engine derives it.
"""

from __future__ import annotations

import numpy as np


def model_config(config: dict):
    import jax.numpy as jnp

    from triton_dist_tpu.models.swa_moe import SwaMoeConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("share", {})
    return SwaMoeConfig.from_hf(
        config, max_seq=config["engine"]["max_seq"],
        dtype=dtypes[config["torch_dtype"]],
        experts_total=share.get("experts_total"),
        expert_offset=share.get("expert_offset", 0))


def weight_key(seed: int):
    """The recipe's key, kept with the reference."""
    from benchmarks.reference.swa_moe import weight_key as key

    return key(seed)


def build(config: dict, seed: int, *, chips: int, ladder: list,
          interpret: bool = False):
    """-> (engine, SwaMoeConfig).  Weights first, pools second."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import swa_moe
    from triton_dist_tpu.serve import ServeEngine

    if chips != 1:
        raise ValueError("this builder places one pipeline stage on one "
                         "chip")
    cfg = model_config(config)
    eng = dict(config["engine"])
    kv_dtype = {"bfloat16": None, "int8": jnp.int8}[eng.pop("kv_dtype")]
    max_seq = eng.pop("max_seq")
    params = swa_moe.init_params(cfg, weight_key(seed))
    jax.block_until_ready(params)
    gen = swa_moe.SwaMoeGenerator(
        cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)), axis="sp",
        max_seq=max_seq, interpret=interpret, kv_dtype=kv_dtype)
    engine = ServeEngine(gen, params, bucket_ladder=ladder, **eng)
    return engine, cfg
