"""Plain float32 reference of the benchmark's language models.

Written from the published descriptions and independent of ``repro``:
pre-norm residual blocks of RMSNorm (gain 1 + w), a mixer and an optional
SwiGLU MLP; an embedding; a final norm; a tied or separate head; next-token
cross entropy with the last position of each row masked.

Mixers:

* ``mamba``: Mamba-2 (arXiv:2405.21060). in_proj to (z, xBC, dt), a
  depthwise causal convolution with bias over xBC, SiLU, the SSD scan
  y_t = Σ_{s<=t} C_t·B_s exp(Σ_{s<k<=t} Δ_k A) Δ_s x_s + D x_t computed in
  the paper's chunked form (here with chunks of 128, whatever the config's
  chunk, so that the reference is a second witness of the scan), the gated
  norm RMSNorm(y · SiLU(z)), out_proj.
* ``attn``: grouped-query attention with rotary embeddings on the two
  halves of each head (the Mistral convention), causal softmax at
  1/sqrt(head_dim), computed one block of queries at a time.

Every matmul runs at ``Precision.HIGHEST``. ``low`` makes every matmul of
the model one in a lower type (the control of the correctness check), as a
low-precision training step takes it: forward, both operands are rounded to
``low``; backward, the cotangent that meets the matmul is rounded to
``low`` too, and the transposed matmuls take the rounded operands. Each
rounding has one scale per operand (the largest magnitude maps to the type's
largest value) where the type's range is narrower than float32's.
``None`` keeps float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["layer", "head_loss", "embed_lookup"]

HI = jax.lax.Precision.HIGHEST
SSD_CHUNK = 128
ATTN_BLOCK = 1024


def _cast(x, low):
    """x rounded to ``low`` and back to float32, with one scale."""
    info = jnp.finfo(low)
    if info.maxexp >= jnp.finfo(jnp.float32).maxexp:  # bfloat16: no scale
        return x.astype(low).astype(jnp.float32)
    s = jnp.max(jnp.abs(x)) / float(info.max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(low).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, low):
    """An operand rounded to ``low``; its cotangent passes through as the
    matmul's transpose made it (a rounding has no derivative of its own)."""
    return _cast(x, low)


def _round_fwd(x, low):
    return _cast(x, low), None


def _round_bwd(low, _, g):
    return (g,)


_round.defvjp(_round_fwd, _round_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(y, low):
    """y unchanged; backward, its cotangent rounded to ``low``."""
    return y


def _round_cotangent_fwd(y, low):
    return y, None


def _round_cotangent_bwd(low, _, g):
    return (_cast(g, low),)


_round_cotangent.defvjp(_round_cotangent_fwd, _round_cotangent_bwd)


def mm(eq: str, a, b, low=None):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low is None:
        return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=jnp.float32)
    y = jnp.einsum(
        eq, _round(a, low), _round(b, low), precision=HI,
        preferred_element_type=jnp.float32,
    )  # fmt: skip
    return _round_cotangent(y, low)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w.astype(jnp.float32))


# ---------------------------------------------------------------- Mamba-2
def _ssd(x, a, b, c, low):
    """Chunked SSD. x (N,S,H,P) already Δ-weighted, a (N,S,H) = Δ·A,
    b and c (N,S,H,Nst). Returns y (N,S,H,P)."""
    n, s, h, p = x.shape
    q = SSD_CHUNK if s % SSD_CHUNK == 0 else s
    nc = s // q
    x = x.reshape(n, nc, q, h, p)
    b = b.reshape(n, nc, q, h, -1)
    c = c.reshape(n, nc, q, h, -1)
    a = a.reshape(n, nc, q, h).transpose(0, 3, 1, 2)  # (N,H,nc,Q)
    acum = jnp.cumsum(a, axis=-1)
    seg = acum[..., :, None] - acum[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    scores = mm("nclhs,ncmhs->nhclm", c, b, low) * decay  # (N,H,nc,Q,Q)
    y_in = mm("nhclm,ncmhp->nclhp", scores, x, low)
    # state at the end of each chunk, then carried across chunks
    to_end = jnp.exp(acum[..., -1:] - acum)  # (N,H,nc,Q)
    b_end = b * to_end.transpose(0, 2, 3, 1)[..., None]
    states = mm("ncmhs,ncmhp->nchps", b_end, x, low)
    chunk_decay = jnp.exp(acum[..., -1])  # (N,H,nc)

    def carry(h0, inp):
        st, dec = inp
        return h0 * dec[..., None, None] + st, h0

    h0 = jnp.zeros(states.shape[:1] + states.shape[2:], jnp.float32)
    _, before = jax.lax.scan(
        carry, h0, (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(2, 0, 1))
    )
    before = before.transpose(1, 0, 2, 3, 4)  # (N,nc,H,P,Nst)
    y_st = mm("nclhs,nchps->nclhp", c, before, low) * jnp.exp(acum).transpose(
        0, 2, 3, 1
    )[..., None]
    return (y_in + y_st).reshape(n, s, h, p)


def _mamba(p, x, m, low):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    g, nst, hp, k = m["ssm_groups"], m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    h = di // hp
    n, s, _ = x.shape
    proj = mm("nsd,de->nse", x, p["in_proj"], low)
    z = proj[..., :di]
    xbc = proj[..., di : 2 * di + 2 * g * nst]
    dt = proj[..., 2 * di + 2 * g * nst :]
    w = p["conv_w"].astype(jnp.float32)  # (K, C)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i : i + s, :] * w[i] for i in range(k))
    conv = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs = conv[..., :di].reshape(n, s, h, hp)
    bm = jnp.repeat(conv[..., di : di + g * nst].reshape(n, s, g, nst), h // g, 2)
    cm = jnp.repeat(conv[..., di + g * nst :].reshape(n, s, g, nst), h // g, 2)
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # (N,S,H)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = _ssd(xs * delta[..., None], delta * a, bm, cm, low)
    y = y + xs * p["D"].astype(jnp.float32)[:, None]
    y = rms(y.reshape(n, s, di) * jax.nn.silu(z), p["norm"], m["norm_eps"])
    return mm("nse,ed->nsd", y, p["out_proj"], low)


# ---------------------------------------------------------------- attention
def _rope(x, theta):
    """x (N,S,H,hd): rotate the two halves of each head by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attn(p, x, m, spec, low):
    n, s, _ = x.shape
    h, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    theta = spec.get("rope_theta") or m["rope_theta"]
    q = mm("nsd,de->nse", x, p["wq"], low)
    k = mm("nsd,de->nse", x, p["wk"], low)
    v = mm("nsd,de->nse", x, p["wv"], low)
    if m.get("qkv_bias"):
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(n, s, h, hd)
    k = k.reshape(n, s, hkv, hd)
    v = v.reshape(n, s, hkv, hd)
    if m.get("qk_norm"):
        q = rms(q, p["q_norm"], m["norm_eps"])
        k = rms(k, p["k_norm"], m["norm_eps"])
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    blk = ATTN_BLOCK if s % ATTN_BLOCK == 0 else s
    window = spec.get("window")
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        logits = mm("nqhd,nkhd->nhqk", qb, k, low) / jnp.sqrt(jnp.float32(hd))
        qpos = i * blk + jnp.arange(blk)[:, None]
        keep = kpos[None, :] <= qpos
        if window is not None:
            keep &= kpos[None, :] > qpos - window
        logits = jnp.where(keep, logits, -jnp.inf)
        return mm("nhqk,nkhd->nqhd", jax.nn.softmax(logits, axis=-1), v, low)

    out = jax.lax.map(block, jnp.arange(s // blk))  # (nb, N, blk, H, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(n, s, h * hd)
    return mm("nse,ed->nsd", out, p["wo"], low)


def _mlp(p, x, low):
    gate = jax.nn.silu(mm("nsd,df->nsf", x, p["gate"], low))
    return mm("nsf,fd->nsd", gate * mm("nsd,df->nsf", x, p["up"], low), p["down"], low)


# ---------------------------------------------------------------- model
def layer(p, x, spec: dict, m: dict, low=None):
    """One pre-norm residual block on x (N, S, D) float32."""
    hid = rms(x, p["ln1"], m["norm_eps"])
    if spec["kind"] == "mamba":
        x = x + _mamba(p["mixer"], hid, m, low)
    elif spec["kind"] == "attn":
        x = x + _attn(p["mixer"], hid, m, spec, low)
    else:
        raise ValueError(f"no reference for layer kind {spec['kind']!r}")
    if spec.get("moe"):
        raise ValueError("no reference for expert layers")
    if m.get("d_ff", 0) > 0:
        x = x + _mlp(p["ffn"], rms(x, p["ln2"], m["norm_eps"]), low)
    return x


def embed_lookup(table, tokens):
    return table.astype(jnp.float32)[tokens]


def head_loss(p, x, tokens, m: dict, low=None, rows: int = 1024):
    """Mean next-token cross entropy of x (R, S, D) against tokens (R, S).

    ``p`` holds ``final_norm`` and ``embed`` (tied) or ``head``. The logits
    are formed ``rows`` positions at a time and recomputed in the backward
    pass, so a large vocabulary never holds all of them."""
    r, s, d = x.shape
    rows = rows if (r * s) % rows == 0 else s
    tgt = jnp.roll(tokens, -1, axis=1)
    keep = jnp.broadcast_to(jnp.arange(s) < s - 1, (r, s)).astype(jnp.float32)
    xf = rms(x, p["final_norm"], m["norm_eps"]).reshape(-1, rows, d)
    tf = tgt.reshape(-1, rows)
    kf = keep.reshape(-1, rows)
    w = p["embed"].T if m.get("tie_embeddings") else p["head"]

    @jax.checkpoint
    def chunk(total, inp):
        xc, tc, kc = inp
        logp = jax.nn.log_softmax(mm("rd,dv->rv", xc, w, low), axis=-1)
        nll = -jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]
        return total + jnp.sum(nll * kc), None

    total, _ = jax.lax.scan(chunk, jnp.float32(0.0), (xf, tf, kf))
    return total / (r * (s - 1))
