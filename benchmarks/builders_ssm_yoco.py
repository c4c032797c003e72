"""Builder of the decoder-hybrid-decoder family (the ``phi4flash`` block:
state-space layers beside window and full attention, one cache read by the
cross-attention layers) for ``builders.build``: named by a configuration
file's ``"builder": "benchmarks.builders_ssm_yoco:build"``.

The same constructors a caller of the library uses
(``SsmYocoConfig.from_hf`` on the file's published keys and the Mamba-1
sizes it lists under ``assumed``, ``init_params`` on the device from the
seed, ``SsmYocoGenerator``, ``ServeEngine``).  Every key of the file's
``engine`` group reaches a constructor here; the window group's block count
and the state group's slots are not among them — the engine derives both.
"""

from __future__ import annotations

import numpy as np

ASSUMED_SIZES = ("d_state", "d_conv", "expand", "dt_rank")


def model_config(config: dict):
    import jax.numpy as jnp

    from triton_dist_tpu.models.ssm_yoco import SsmYocoConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return SsmYocoConfig.from_hf(
        config, max_seq=config["engine"]["max_seq"],
        dtype=dtypes[config["torch_dtype"]],
        **{k: config["assumed"][k] for k in ASSUMED_SIZES})


def weight_key(seed: int):
    """The recipe's key, kept with the reference."""
    from benchmarks.reference.ssm_yoco import weight_key as key

    return key(seed)


def build(config: dict, seed: int, *, chips: int, ladder: list,
          interpret: bool = False):
    """-> (engine, SsmYocoConfig).  Weights first, pools second."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import ssm_yoco
    from triton_dist_tpu.serve import ServeEngine

    if chips != 1:
        raise ValueError("this builder places the whole model on one chip")
    cfg = model_config(config)
    eng = dict(config["engine"])
    kv_dtype = {"bfloat16": None, "int8": jnp.int8}[eng.pop("kv_dtype")]
    max_seq = eng.pop("max_seq")
    params = ssm_yoco.init_params(cfg, weight_key(seed))
    jax.block_until_ready(params)
    gen = ssm_yoco.SsmYocoGenerator(
        cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)), axis="sp",
        max_seq=max_seq, interpret=interpret, kv_dtype=kv_dtype)
    engine = ServeEngine(gen, params, bucket_ladder=ladder, **eng)
    return engine, cfg
