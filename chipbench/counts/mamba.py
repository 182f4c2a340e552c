"""Forward FLOPs per token of one Mamba-2 (SSD) mixer, as the paper's
chunked form computes it with chunk Q: the in and out projections, the
causal convolution over x, B and C, and the SSD itself. C·Bᵀ is one (Q, Q)
block per B/C group, shared by the group's heads; each head then applies
its decay mask to it, multiplies by x (Q·P) and makes and reads its chunk
state (2·P·N).

The inner width is the model's ``ssm_d_inner`` where it gives one (above
0), else ``ssm_expand · d_model``; the heads are that width over
``ssm_head_dim``."""


def fwd(model: dict, spec: dict, seq_len: int) -> float:
    d = model["d_model"]
    di = model.get("ssm_d_inner") or model["ssm_expand"] * d
    g, n = model["ssm_groups"], model["ssm_state"]
    p, q = model["ssm_head_dim"], model["ssm_chunk"]
    h = di // p
    proj = 2 * d * (2 * di + 2 * g * n + h) + 2 * di * d
    conv = 2 * model["ssm_conv"] * (di + 2 * g * n)
    ssd = 2 * (g * q * n + h * (q * p + 2 * p * n))
    return proj + conv + ssd
