"""deepseek-v2-lite-16b [moe] — MLA (kv_lora=512, no query LoRA) with YaRN
rope scaling, 2 shared + 64 routed top-6 experts (softmax, greedy, not
renormalised, sequence-wise balance loss), first layer dense.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=0,               # MLA defines per-component head dims
    d_ff=1408,                # routed-expert hidden size
    vocab_size=102400,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(type="yarn", factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    # aux_loss_alpha is not in the published config.json; 0.001 is the
    # modelling code's default. routed_scaling_factor is 1: the gates go
    # on unscaled
    moe=MoEConfig(n_experts=64, top_k=6, n_shared_experts=2, expert_d_ff=1408,
                  norm_topk_prob=False, seq_aux=True, aux_loss_coef=0.001),
    first_k_dense=1,
    dense_d_ff=10944,
)

SMOKE = CONFIG.with_(
    n_layers=3, d_model=128, n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=512,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=MoEConfig(n_experts=8, top_k=2, n_shared_experts=1, expert_d_ff=64,
                  norm_topk_prob=False, seq_aux=True, aux_loss_coef=0.001),
    first_k_dense=1, dense_d_ff=256)
