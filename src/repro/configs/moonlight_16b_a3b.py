"""moonlight-16b-a3b [moe] — DeepSeek-V3 layers: latent attention (MLA) and
64 routed experts top-6 with 2 shared experts, one leading dense layer.

27L d_model=2048 16H, MLA kv_lora=512 qk_nope=128 qk_rope=64 v=128,
dense d_ff=11264, experts 64 x 1408 top-6, shared 2 x 1408, vocab=163840,
untied [hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437]

Routing is DeepSeek-V3's noaux_tc with one group: sigmoid scores, top-6
chosen on score + a selection bias, the chosen scores renormalised and
scaled by 2.446.  Embeddings are not scaled by sqrt(d_model).  ~16 B total
/ ~3 B active parameters.
"""
from repro.configs.base import ArchConfig, AttnConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=192,                       # q/k head width (nope 128 + rope 64)
    d_ff=11264,                       # the leading dense layer's width
    vocab=163840,
    attn=AttnConfig(rope_theta=50000.0),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408,
                  shared_expert=True, d_shared=2816, score="sigmoid",
                  routed_scale=2.446),
    pattern=(("mla", "moe"),),
    first_k_dense=1,
    scale_embed=False,
    norm_eps=1e-5,
)
