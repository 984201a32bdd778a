"""repro.models — composable model zoo with ABFP-dispatched matmuls.

Serving ownership (family -> ModelRunner, see ``repro.serving.runners``):
decoder-only dense/MoE families (smollm, tinyllama, gemma, chatglm,
granite, kimi, phi-vision) serve through ``DecoderRunner``; ssm/hybrid
families (xlstm, recurrentgemma) through ``RecurrentRunner`` (fixed-size
decode state — no paging, no preemption); encoder-decoder families
(whisper) through ``EncDecRunner`` (one ``encode`` +
``encode_cross_kv`` pass at admission, cached per slot).  Model code
stays engine-agnostic: ``decode_step`` / ``prefill`` take an optional
``enc_kv`` and never import serving.
"""

from repro.models.layers import (  # noqa: F401
    Numerics,
    attention_block,
    chunked_attention,
    decode_attention,
    im2col,
    layernorm,
    mlp_block,
    rmsnorm,
    rope,
)
from repro.models.lm import (  # noqa: F401
    decode_step,
    encode,
    encode_cross_kv,
    forward,
    forward_capture,
    fused_decode_layers,
    init_decode_state,
    init_params,
    param_count,
    prefill,
    sample_tokens,
)
from repro.models.packing import (  # noqa: F401
    pack_model_params,
    packed_param_bytes,
)
from repro.models import frontends, moe, recurrent  # noqa: F401
