"""Forward FLOPs per token of one GQA attention mixer: the q, k, v and o
projections, and QKᵀ and PV over the keys a token attends, which causal
attention puts at S/2 on average (at most the layer's window)."""


def fwd(model: dict, spec: dict, seq_len: int) -> float:
    d, h, hkv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    hd = model["head_dim"]
    window = spec.get("window")
    ctx = seq_len / 2 if window is None else min(seq_len / 2, window)
    proj = 2 * d * h * hd + 2 * 2 * d * hkv * hd + 2 * h * hd * d
    return proj + 2 * 2 * ctx * h * hd
