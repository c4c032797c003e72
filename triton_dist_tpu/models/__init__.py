"""End-to-end model families wired through the distributed kernels.

Reference analog: the reference ships no trainer — its model story is the
LLaMA-shape test configs (test_ag_gemm.py ``--shape_id``) and inference
layers.  The TPU build provides actual models: a Llama-style dense
transformer (``llama.py``) and a Mixtral-style MoE (``moe.py``), both
running forward AND backward through the overlapped kernels; and, for
serving, the latent-attention + sigmoid-routed expert block as one chip's
share of an expert-parallel deployment (``mla_moe.py``).
"""

from triton_dist_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    init_params,
    forward_shard,
    loss_shard,
    make_forward,
    make_train_step,
)
from triton_dist_tpu.models.moe import (  # noqa: F401
    MoEConfig,
    init_params as moe_init_params,
    make_forward as moe_make_forward,
    make_train_step as moe_make_train_step,
    place_params as moe_place_params,
)
from triton_dist_tpu.models.pp import (  # noqa: F401
    init_pp_params,
    make_pp_train_step,
    place_pp_params,
    pp_param_specs,
)
from triton_dist_tpu.models.cp import (  # noqa: F401
    cp_param_specs,
    make_cp_forward,
    make_cp_train_step,
    place_cp_params,
)
from triton_dist_tpu.models.generate import (  # noqa: F401
    GenerationState,
    Generator,
)
from triton_dist_tpu.models.generate_moe import (  # noqa: F401
    MoEGenerator,
    place_params_serving,
)
from triton_dist_tpu.models.mla_moe import (  # noqa: F401
    LatentPoolUnsupported,
    MlaMoeConfig,
    MlaMoeGenerator,
)
from triton_dist_tpu.models.sampling import (  # noqa: F401
    make_sampler,
    sample_logits,
)
from triton_dist_tpu.models.llama_w8a8 import (  # noqa: F401
    make_w8a8_forward,
    place_w8a8_params,
    quantize_params_w8a8,
)
from triton_dist_tpu.models.beam import beam_search  # noqa: F401
from triton_dist_tpu.models.speculative import (  # noqa: F401
    SpeculativeGenerator,
    SpeculativeSampler,
)
