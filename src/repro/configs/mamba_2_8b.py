"""mamba-2.8b [ssm] — 64 Mamba-1 layers, d_model=2560, no MLP,
vocab=50280, tied embeddings.  [hf:state-spaces/mamba-2.8b-hf]

The published config (https://huggingface.co/state-spaces/mamba-2.8b-hf,
``config.json``): ``num_hidden_layers`` 64, ``hidden_size`` 2560,
``intermediate_size`` (d_inner) 5120 (``expand`` 2), ``state_size`` 16,
``time_step_rank`` 160 (= 2560 / 16, which ``ArchConfig.dt_rank``
derives), ``conv_kernel`` 4, ``use_conv_bias`` true, ``use_bias`` false
(no projection bias), ``rms_norm`` true with ``layer_norm_epsilon``
1e-5, ``residual_in_fp32`` true, ``vocab_size`` 50280 (50277 padded to
a multiple of 8), ``tie_word_embeddings`` true.

A Mamba model has no attention: ``num_heads``, ``num_kv_heads`` (1 and
1) and ``d_ff`` (0) are placeholders that ArchConfig requires, as in
``xlstm_350m``.  Every layer is one ``mamba`` block (``models/ssm.py``),
so the pruning engine makes one segment per layer.
"""

from repro.models.base import ArchConfig

CONFIG = ArchConfig(
    name="mamba-2.8b",
    family="ssm",
    num_layers=64,
    d_model=2560,
    num_heads=1,
    num_kv_heads=1,
    d_ff=0,
    vocab_size=50280,
    period=("mamba",),
    mlp_kind="none",
    ssm_state=16,
    ssm_expand=2,
    ssm_conv=4,
    tie_embeddings=True,
    norm_eps=1e-5,
    dtype="bfloat16",
    residual_in_fp32=True,
)

SMOKE = ArchConfig(
    name="mamba-2.8b-smoke",
    family="ssm",
    num_layers=2,
    d_model=64,
    num_heads=1,
    num_kv_heads=1,
    d_ff=0,
    vocab_size=256,
    period=("mamba",),
    mlp_kind="none",
    ssm_state=16,
    ssm_expand=2,
    ssm_conv=4,
    tie_embeddings=True,
    norm_eps=1e-5,
    dtype="float32",
    residual_in_fp32=True,
)
