"""granite-4.0-h-micro [hybrid]: 40L d_model=2048, a period of 10 layers
(5 Mamba-2, 1 GQA attention, 4 Mamba-2), a SwiGLU MLP of 8192 after every
layer, GQA 32q/8kv heads of 64 with no positional encoding, vocab=100352,
ssm_state=128, embedding x12, residual branches x0.22, logits /8.
[huggingface.co/ibm-granite/granite-4.0-h-micro]"""
from repro.configs.base import ATTN_GLOBAL, MIXER_SSM, ModelConfig, SSMConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro", family="hybrid",
        n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, vocab_size=100352,
        pattern=(MIXER_SSM,) * 5 + (ATTN_GLOBAL,) + (MIXER_SSM,) * 4,
        use_rope=False, query_scale=0.015625,
        ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                      n_groups=1, chunk=256, site_mlp=True,
                      embedding_multiplier=12.0, residual_multiplier=0.22,
                      logits_scaling=8.0),
        tie_embeddings=True, norm_eps=1e-5, max_seq_len=131072,
    )
