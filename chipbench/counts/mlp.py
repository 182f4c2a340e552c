"""Forward FLOPs per token of one dense gated FFN (SwiGLU or GeGLU): the
gate, up and down projections, 3 · 2 · d_model · d_ff."""


def fwd(model: dict, spec: dict, seq_len: int) -> float:
    return 6 * model["d_model"] * model["d_ff"]
