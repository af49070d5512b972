"""minicpm-2b [dense] — llama-like MHA; trained with the WSD schedule.

40L, d_model=2304, 36 heads (kv=36), d_ff=5760, vocab 122753.
Source: https://huggingface.co/openbmb/MiniCPM-2B-sft-bf16 (arXiv:2404.06395).
WSD schedule supported in repro.optim.schedules.

Published keys not modelled here: the muP scalars ``scale_emb`` 12,
``scale_depth`` 1.4 and ``dim_model_base`` 256, which scale the embedding,
the residual branches and the LM-head input.  The served verifier's
synthetic one-layer target (``launch/serve.py --backend spec``) has no
embedding or residual, and a constant scale of the LM-head input is
absorbed by its random head.
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="minicpm-2b",
    family="dense",
    n_layers=40,
    d_model=2304,
    n_heads=36,
    n_kv_heads=36,
    d_ff=5760,
    vocab_size=122_753,
    head_dim=64,
    tie_embeddings=True,
)

REDUCED = CONFIG.reduced()
