"""Model FLOPs per token, from the configuration's sizes alone.

What the forward pass *requires*: two FLOPs per multiply-add of every matrix
multiplication a token passes through (attention projections, the MLP or the
``num_experts_per_tok`` routed experts and the router, the lm-head) plus the
attention scores and the weighted sum over the keys a query may see.  The
embedding lookup is a gather and counts nothing.  Attention is halved for
causality and clipped at the window: position ``i`` sees ``min(i + 1, W)``
keys.  Training is three times the forward pass (backward = 2 x forward);
recomputation is not credited.
"""


def mean_keys(seq_len, window=0):
    """Mean number of keys a query of a ``seq_len`` sequence attends to."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    full = window * (window + 1) / 2.0          # positions 0 .. W-1
    return (full + (seq_len - window) * window) / seq_len


def matmul_params_per_token(cfg, depth):
    """Weights a token is multiplied with: (per layer x depth, lm-head)."""
    d, i = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    experts = cfg.get("num_local_experts", 0)
    if experts:
        mlp = cfg["num_experts_per_tok"] * 3 * d * i + d * experts
    else:
        mlp = 3 * d * i
    return depth * (attn + mlp), d * cfg["vocab_size"]


def forward_flops_per_token(cfg, depth, seq_len):
    layers, head = matmul_params_per_token(cfg, depth)
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    keys = mean_keys(seq_len, cfg.get("sliding_window") or 0)
    attention = depth * 2 * 2 * h * dh * keys       # QK^T and PV
    return 2 * (layers + head) + attention


def train_flops_per_token(cfg, depth, seq_len):
    return 3 * forward_flops_per_token(cfg, depth, seq_len)


def lm_head_share(cfg, depth, seq_len):
    _, head = matmul_params_per_token(cfg, depth)
    return 2 * head / forward_flops_per_token(cfg, depth, seq_len)
