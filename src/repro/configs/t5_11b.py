"""T5-11B (Raffel et al., JMLR 2020, arXiv:1910.10683) with its own block.

Widths from google-t5/t5-11b's ``config.json``
(https://huggingface.co/google-t5/t5-11b/blob/main/config.json): d_model
1024, d_kv 128, 128 heads, d_ff 65536, 24 encoder and 24 decoder layers,
vocab 32128, 32 relative-attention buckets, ReLU feed-forward, layer-norm
epsilon 1e-6, tied embeddings. The bucket function's max distance, 128, is
Mesh TF's ``_relative_position_bucket`` default (the config does not list
it). Positions enter only through the relative bias (``t5_block``): no
RoPE. ``n_layers`` counts encoder layers; the decoder mirrors it.
Dropout (0.1 in the config) is left out. DynaPipe's Table 1 trains T5 at
these widths; ``t5-paper`` is the same widths on GPT's block.
"""
from repro.configs.base import ArchConfig, LayerSpec

CONFIG = ArchConfig(
    name="t5-11b",
    family="encdec",
    source="[arXiv:1910.10683; hf google-t5/t5-11b config.json]",
    n_layers=24,
    d_model=1024,
    n_heads=128,
    n_kv_heads=128,
    d_head=128,
    d_ff=65536,
    vocab=32128,
    layer_pattern=(LayerSpec("attn"),),
    use_rope=False,
    rope_theta=0.0,
    rel_attn_buckets=32,
    rel_attn_max_distance=128,
    tie_embeddings=True,
    mlp_gated=False,
    act="relu",
    norm_eps=1e-6,
)
