"""The wire-codec layer: every compressor's quantize -> pack -> collective
-> dequantize pipeline lives here.

A :class:`WireCodec` turns a normalized float tensor into the exact array
that travels over the interconnect (``encode``), recovers code values from
gathered wire bytes (``decode``), and maps averaged codes back to values
(``expand``).  ``wire_bits`` reports the *actual* byte size of the encoded
array — with b<=4 codes nibble-packed two-per-int8-lane, so wire accounting
and array bytes agree (a b=4 tensor really travels at half the int8 bytes).

Codecs are constructed through a registry — :func:`make_codec` resolves a
name (``available_codecs()`` lists them) to a factory and validates knobs
against the codec's dataclass fields. Registered codecs:

  * ``float32`` :class:`Float32Codec`  — identity fp32 wire (PowerSGD
    factors, TopK's dense-simulated sparse payload);
  * ``log`` :class:`LogQuantCodec` — the paper's Eq. 5/6 log-quantizer,
    with two backends: ``jnp_ref`` (pure jnp, default) and ``pallas`` (the
    fused TPU kernels in ``repro.kernels.log_quant``, interpret-mode on
    the CPU), validated bit-for-bit against each other;
  * ``qsgd`` :class:`QSGDCodec`     — stochastic uniform quantization
    (Alistarh et al. 2017), the canonical baseline the paper cites;
  * ``dlog`` :class:`DitheredLogQuantCodec` — the log grid with unbiased
    stochastic (dithered) rounding and, at ``dp_epsilon > 0``, Gaussian
    noise calibrated to a per-use DP budget (arXiv 2304.13545: the
    quantizer's own randomness is the privacy mechanism);
  * ``lrq`` :class:`LayeredRandQuantCodec` — layered randomized
    quantization (arXiv 2312.07060): each element is stochastically
    rounded on one of ``n_layers`` nested coarsenings of the log grid,
    drawn per use — same wire format and bits as ``log``, wider noise
    support, Gaussian-equivalent epsilon proxy.

PRNG contract: codecs declare ``requires_key``. Randomized codecs
*require* the keyword-only ``key`` in ``codes``/``encode``; deterministic
codecs *reject* one (a silently-ignored key would make a run look
reproducible while it isn't). Handlers split per-leaf keys
deterministically from the compressor state key (see
``repro.core.compressors``), so reruns reproduce bit-for-bit.

:func:`codec_phase` is the one collective primitive all compressors share:
it scales (fused pmax), encodes, ships (ONE fused flat all-gather when
``fuse=True``, else per-tensor gathers), decodes and averages a *list* of
tensors.  PowerSGD's P-phase and Q-phase, LQ-SGD's quantized factor wire,
QSGD's payload and TopK's dense simulation are all single calls into it.
"""
from __future__ import annotations

import ast
import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.comm import AxisComm, CommRecord
from repro.core.quantization import (LogQuantConfig, code_dtype, dequantize,
                                     log_compress, log_expand, quantize)
from repro.core.wire import SymmetricWire, as_wire

__all__ = [
    "WireCodec",
    "Float32Codec",
    "LogQuantCodec",
    "QSGDCodec",
    "DitheredLogQuantCodec",
    "LayeredRandQuantCodec",
    "register_codec",
    "make_codec",
    "available_codecs",
    "make_wire_codec",
    "codec_phase",
    "pack_nibbles",
    "unpack_nibbles",
    "packed_wire_bits",
    "CODEC_BACKENDS",
]

CODEC_BACKENDS = ("jnp_ref", "pallas")
# jax.named_scope tags of codec_phase's two halves (HLO metadata only)
ENCODE_SCOPE = "codec.encode"
DECODE_SCOPE = "codec.decode"


# --------------------------------------------------------------------------
# the codec registry: all construction goes through make_codec
# --------------------------------------------------------------------------

_CODEC_REGISTRY: dict[str, type] = {}


def register_codec(name: str) -> Callable[[type], type]:
    """Class decorator: register a WireCodec subclass under ``name``."""
    def deco(cls: type) -> type:
        if name in _CODEC_REGISTRY:
            raise ValueError(f"codec {name!r} already registered "
                             f"({_CODEC_REGISTRY[name].__name__})")
        _CODEC_REGISTRY[name] = cls
        setattr(cls, "codec_name", name)
        return cls
    return deco


def available_codecs() -> tuple[str, ...]:
    """Registered codec names, sorted."""
    return tuple(sorted(_CODEC_REGISTRY))


def _parse_codec_spec(spec: str) -> tuple[str, dict]:
    """'name' or 'name:knob=value,knob=value' -> (name, knobs).

    Values parse as Python literals where possible ('4' -> 4,
    '0.5' -> 0.5, 'True' -> True) and stay strings otherwise
    ('pallas' -> 'pallas')."""
    name, _, rest = spec.partition(":")
    knobs: dict = {}
    if rest:
        for item in rest.split(","):
            k, sep, v = item.partition("=")
            if not sep or not k:
                raise ValueError(
                    f"bad codec spec item {item!r} in {spec!r}; "
                    "expected 'name:knob=value,...'")
            try:
                knobs[k.strip()] = ast.literal_eval(v.strip())
            except (ValueError, SyntaxError):
                knobs[k.strip()] = v.strip()
    return name.strip(), knobs


def make_codec(spec: str, **knobs) -> "WireCodec":
    """The registry entry point: build a codec from a name + knobs.

    ``spec`` is a registered name ('log', 'dlog', ...) optionally carrying
    inline knobs ('dlog:bits=4,dp_epsilon=8'); explicit keyword knobs
    override inline ones. Knob names are validated against the codec's
    dataclass fields so a typo fails loudly with the accepted set.
    """
    name, inline = _parse_codec_spec(spec)
    cls = _CODEC_REGISTRY.get(name)
    if cls is None:
        raise ValueError(
            f"unknown codec {name!r}; available: {', '.join(available_codecs())}")
    merged = {**inline, **knobs}
    accepted = {f.name for f in dataclasses.fields(cls) if f.init}
    unknown = sorted(set(merged) - accepted)
    if unknown:
        raise ValueError(
            f"codec {name!r} does not accept knob(s) {unknown}; "
            f"accepted: {sorted(accepted)}")
    return cls(**merged)


# --------------------------------------------------------------------------
# bit packing: two 4-bit two's-complement codes per int8 lane
# --------------------------------------------------------------------------

def pack_nibbles(codes: jax.Array) -> jax.Array:
    """Signed codes in [-8, 7] (any shape) -> 1-D int8, byte i = c[2i] | c[2i+1]<<4."""
    flat = codes.reshape(-1).astype(jnp.int32)
    if flat.size % 2:
        flat = jnp.pad(flat, (0, 1))
    lo, hi = flat[0::2], flat[1::2]
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.int8)


def unpack_nibbles(packed: jax.Array, numel: int) -> jax.Array:
    """Packed int8 (..., nbytes) -> signed int32 codes (..., numel)."""
    v = packed.astype(jnp.int32) & 0xFF
    lo = v & 0xF
    hi = (v >> 4) & 0xF
    sext = lambda n: (n ^ 8) - 8  # sign-extend a 4-bit two's-complement nibble
    codes = jnp.stack([sext(lo), sext(hi)], axis=-1)
    return codes.reshape(packed.shape[:-1] + (-1,))[..., :numel]


def packed_wire_bits(numel: int, bits: int) -> int:
    """Exact bits of the encoded array: nibble-packed int8 for b<=4, int8
    for b<=8, int16 above — matching the containers ``encode`` emits."""
    if bits <= 4:
        return ((numel + 1) // 2) * 8
    if bits <= 8:
        return numel * 8
    return numel * 16


# --------------------------------------------------------------------------
# codecs
# --------------------------------------------------------------------------

class WireCodec:
    """Protocol: what a compressor needs to put a tensor on the wire.

    ``codes``   normalized values -> integer (or identity float) code array,
                same shape as the input (pre-packing; ``psum_sim`` wire and
                the averaging math use these);
    ``encode``  normalized values -> the 1-D wire array (packed for b<=4);
    ``decode``  gathered wire array (..., nbytes|numel) -> float code values
                (..., numel);
    ``expand``  (possibly averaged) float codes -> normalized values;
    ``wire_bits``  exact bits of ``encode``'s output for ``numel`` elements;
    ``scale_bits`` bits of scale sideband (0 when ``needs_scale`` is False).

    PRNG contract: ``requires_key`` declares whether ``codes``/``encode``
    consume randomness. Randomized codecs raise if the keyword-only ``key``
    is missing; deterministic codecs raise if one is passed (a silently
    dropped key is a reproducibility bug waiting to be read as noise).

    Privacy contract: ``privacy_sigma()`` is the std of injected noise in
    normalized units (0.0 when deterministic) and ``epsilon_per_use(delta)``
    the per-message DP epsilon under the Gaussian-mechanism convention of
    ``repro.core.privacy.accounting`` (``inf`` when there is no guarantee).
    ``epsilon_kind`` labels the claim: 'calibrated' (noise sized from a
    requested budget), 'gaussian_equiv' (proxy from measured noise
    variance), or None.
    """

    bits: int = 32
    needs_scale: bool = True
    requires_key: bool = False
    epsilon_kind: str | None = None
    codec_name: str = ""

    def codes(self, x: jax.Array, *, key: jax.Array | None = None) -> jax.Array:
        raise NotImplementedError

    def encode(self, x: jax.Array, *, key: jax.Array | None = None) -> jax.Array:
        raise NotImplementedError

    def decode(self, wire: jax.Array, numel: int) -> jax.Array:
        raise NotImplementedError

    def expand(self, codes: jax.Array) -> jax.Array:
        raise NotImplementedError

    def wire_bits(self, numel: int) -> int:
        raise NotImplementedError

    def scale_bits(self, n_scales: int) -> int:
        return 32 * n_scales if self.needs_scale else 0

    def privacy_sigma(self) -> float:
        return 0.0

    def epsilon_per_use(self, delta: float = 1e-5) -> float:
        return math.inf

    def _check_key(self, key: jax.Array | None) -> None:
        if self.requires_key and key is None:
            raise ValueError(
                f"{type(self).__name__} is randomized (requires_key=True) "
                "and needs a PRNG key: call codes/encode with key=...")
        if not self.requires_key and key is not None:
            raise ValueError(
                f"{type(self).__name__} is deterministic (requires_key="
                "False) and rejects a PRNG key — it would be silently "
                "unused; drop the key= argument")


@register_codec("float32")
@dataclasses.dataclass(frozen=True)
class Float32Codec(WireCodec):
    """Identity fp32 wire: 'codes' are the values themselves."""

    bits: int = 32
    needs_scale: bool = False

    def codes(self, x, *, key=None):
        self._check_key(key)
        return x.astype(jnp.float32)

    def encode(self, x, *, key=None):
        self._check_key(key)
        return x.astype(jnp.float32).reshape(-1)

    def decode(self, wire, numel):
        return wire.astype(jnp.float32)

    def expand(self, codes):
        return codes

    def wire_bits(self, numel):
        return numel * 32


@register_codec("log")
@dataclasses.dataclass(frozen=True)
class LogQuantCodec(WireCodec):
    """Paper Eq. 5/6 log-quantizer. ``backend='pallas'`` routes the
    quantize/dequantize math and the b<=4 nibble pack through the Pallas
    kernels (interpret mode on the CPU); both backends emit identical bytes."""

    bits: int = 8
    alpha: float = 10.0
    backend: str = "jnp_ref"
    needs_scale: bool = True

    def __post_init__(self):
        if self.backend not in CODEC_BACKENDS:
            raise ValueError(
                f"unknown quant backend {self.backend!r}; options: {CODEC_BACKENDS}")

    @property
    def _cfg(self) -> LogQuantConfig:
        return LogQuantConfig(bits=self.bits, alpha=self.alpha)

    def codes(self, x, *, key=None):
        self._check_key(key)
        if self.backend == "pallas":
            from repro.kernels.log_quant import log_quantize_pallas
            return log_quantize_pallas(x, jnp.float32(1.0), bits=self.bits,
                                       alpha=self.alpha)
        return quantize(x, self._cfg)

    def encode(self, x, *, key=None):
        self._check_key(key)
        if self.bits <= 4 and self.backend == "pallas":
            # single fused pallas_call: quantize + nibble-pack in one VMEM
            # pass, so the int8 codes never round-trip through HBM between
            # two kernel launches (bytes identical to the jnp packer)
            from repro.kernels.log_quant import log_quantize_pack_pallas
            return log_quantize_pack_pallas(x, jnp.float32(1.0),
                                            bits=self.bits, alpha=self.alpha)
        c = self.codes(x)
        if self.bits <= 4:
            return pack_nibbles(c)
        return c.reshape(-1)

    def decode(self, wire, numel):
        if self.bits <= 4:
            return unpack_nibbles(wire, numel).astype(jnp.float32)
        return wire.astype(jnp.float32)

    def expand(self, codes):
        if self.backend == "pallas":
            from repro.kernels.log_quant import log_dequantize_pallas
            return log_dequantize_pallas(codes, jnp.float32(1.0), bits=self.bits,
                                         alpha=self.alpha)
        return dequantize(codes, self._cfg)

    def wire_bits(self, numel):
        return packed_wire_bits(numel, self.bits)


@register_codec("qsgd")
@dataclasses.dataclass(frozen=True)
class QSGDCodec(WireCodec):
    """QSGD stochastic uniform quantization: E[expand(codes(x))] = x.
    Requires a per-call PRNG ``key`` (per-worker, per-tensor, per-step).
    Its rounding noise has bounded support, so ``epsilon_per_use`` stays
    ``inf`` — no (epsilon, delta) claim under the Gaussian accountant."""

    bits: int = 8
    backend: str = "jnp_ref"
    needs_scale: bool = True
    requires_key = True

    def __post_init__(self):
        if self.backend not in CODEC_BACKENDS:
            raise ValueError(
                f"unknown quant backend {self.backend!r}; options: {CODEC_BACKENDS}")

    @property
    def levels(self) -> int:
        return (1 << (self.bits - 1)) - 1

    def codes(self, x, *, key=None):
        self._check_key(key)
        x = x.astype(jnp.float32)
        y = jnp.abs(x) * self.levels
        lo = jnp.floor(y)
        rnd = jax.random.uniform(key, x.shape)
        q = (lo + (rnd < (y - lo))) * jnp.sign(x)
        q = jnp.clip(q, -self.levels, self.levels)
        return q.astype(jnp.int8 if self.bits <= 8 else jnp.int16)

    def encode(self, x, *, key=None):
        c = self.codes(x, key=key)
        if self.bits <= 4:
            if self.backend == "pallas":
                from repro.kernels.log_quant import pack_nibbles_pallas
                return pack_nibbles_pallas(c)
            return pack_nibbles(c)
        return c.reshape(-1)

    def decode(self, wire, numel):
        if self.bits <= 4:
            return unpack_nibbles(wire, numel).astype(jnp.float32)
        return wire.astype(jnp.float32)

    def expand(self, codes):
        return codes.astype(jnp.float32) / self.levels

    def wire_bits(self, numel):
        return packed_wire_bits(numel, self.bits)


def _value_unbiased_round(x: jax.Array, q: jax.Array, step: jax.Array | float,
                          levels: int, alpha: float,
                          key: jax.Array) -> jax.Array:
    """Stochastically round continuous log-domain codes ``q`` onto the grid
    of multiples of ``step`` (clipped at +-levels), unbiased in the VALUE
    domain: E[log_expand(c/L)] == log_expand(q/L) exactly.

    Log-domain dithering would be biased through the convex expand map
    (the same Jensen gap PR 1 fixed in the LQ-SGD mean); instead the
    rounding probability is taken between the two candidate
    *reconstruction values* v0, v1: p = (x - v0) / (v1 - v0).
    """
    g0 = jnp.floor(q / step) * step
    g1 = jnp.clip(g0 + step, -levels, levels)
    g0 = jnp.clip(g0, -levels, levels)
    v0 = log_expand(g0 / levels, alpha)
    v1 = log_expand(g1 / levels, alpha)
    v = log_expand(q / levels, alpha)  # == x up to fp error; recomputed so
    #   additive noise applied in x-space stays consistent with q
    p = jnp.clip((v - v0) / jnp.maximum(v1 - v0, 1e-12), 0.0, 1.0)
    u = jax.random.uniform(key, x.shape)
    return jnp.where(u < p, g1, g0)


@register_codec("dlog")
@dataclasses.dataclass(frozen=True)
class DitheredLogQuantCodec(LogQuantCodec):
    """Stochastic/dithered log-quantizer with an optional per-use DP budget
    (arXiv 2304.13545: quantization randomness as the privacy mechanism).

    Same wire format, packing and ``wire_bits`` as :class:`LogQuantCodec`.
    With ``dither=True`` codes are stochastically rounded, unbiased in the
    value domain (E over keys of expand(codes(x)) == x). With
    ``dp_epsilon > 0``, Gaussian noise calibrated by
    ``accounting.gaussian_sigma(dp_epsilon, dp_delta)`` is added to the
    normalized value *before* rounding — quantization is post-processing,
    so the (dp_epsilon, dp_delta) guarantee survives it per use.

    The zero-noise configuration (``dither=False, dp_epsilon=0``) is
    deterministic, rejects keys, and is bit-for-bit the plain ``log``
    codec — it delegates to it outright.
    """

    dither: bool = True
    dp_epsilon: float = 0.0
    dp_delta: float = 1e-5

    def __post_init__(self):
        super().__post_init__()
        if self.dp_epsilon < 0:
            raise ValueError(f"dp_epsilon must be >= 0, got {self.dp_epsilon}")
        if not 0.0 < self.dp_delta < 1.0:
            raise ValueError(f"dp_delta must be in (0, 1), got {self.dp_delta}")

    @property
    def requires_key(self) -> bool:  # type: ignore[override]
        return bool(self.dither or self.dp_epsilon > 0)

    @property
    def epsilon_kind(self) -> str | None:  # type: ignore[override]
        return "calibrated" if self.dp_epsilon > 0 else None

    def privacy_sigma(self) -> float:
        if self.dp_epsilon <= 0:
            return 0.0
        # lazy import: repro.core.privacy.__init__ pulls the GIA harness,
        # which imports the compressors, which import this module
        from repro.core.privacy.accounting import gaussian_sigma
        return gaussian_sigma(self.dp_epsilon, self.dp_delta)

    def epsilon_per_use(self, delta: float = 1e-5) -> float:
        del delta  # calibrated against self.dp_delta, not the caller's
        return self.dp_epsilon if self.dp_epsilon > 0 else math.inf

    def codes(self, x, *, key=None):
        self._check_key(key)
        if key is None:  # zero-noise: exactly the deterministic codec
            return super().codes(x)
        x = x.astype(jnp.float32)
        kn, ku = jax.random.split(key)
        sigma = self.privacy_sigma()
        if sigma > 0.0:
            x = x + sigma * jax.random.normal(kn, x.shape)
        lv = self._cfg.levels
        q = log_compress(x, self.alpha) * lv
        if self.dither:
            c = _value_unbiased_round(x, q, 1.0, lv, self.alpha, ku)
        else:  # noise-only mode: deterministic rounding of the noised value
            c = jnp.round(q)
        return jnp.clip(c, -lv, lv).astype(code_dtype(self.bits))

    def encode(self, x, *, key=None):
        self._check_key(key)
        if key is None:
            return super().encode(x)
        # randomized path: jnp math regardless of backend (the pallas fused
        # quantize+pack kernel is deterministic); bytes match pack_nibbles
        c = self.codes(x, key=key)
        if self.bits <= 4:
            return pack_nibbles(c)
        return c.reshape(-1)


@register_codec("lrq")
@dataclasses.dataclass(frozen=True)
class LayeredRandQuantCodec(LogQuantCodec):
    """Layered randomized quantizer (arXiv 2312.07060).

    Each element independently draws one of ``n_layers`` nested
    coarsenings of the log grid — layer j keeps the codes that are
    multiples of 2^j — and is stochastically rounded onto it, unbiased in
    the value domain. Coarser layers inject more rounding noise, so the
    layer mixture widens the output distribution (the privacy mechanism)
    while the wire format, packing and ``wire_bits`` stay exactly those of
    the base ``log`` codec: every emitted code is a valid b-bit code, and
    the receiver needs no knowledge of the sender's layer draws.

    ``epsilon_per_use`` is a Gaussian-equivalent proxy from the mixture's
    rounding-noise variance (``epsilon_kind='gaussian_equiv'``): the noise
    has bounded support, so this is a comparison heuristic, not a
    calibrated guarantee. The zero-noise configuration
    (``n_layers=1, dither=False``) is bit-for-bit the plain ``log`` codec.
    """

    n_layers: int = 2
    dither: bool = True

    def __post_init__(self):
        super().__post_init__()
        if not 1 <= self.n_layers <= self.bits - 1:
            raise ValueError(
                f"n_layers must be in [1, bits-1] = [1, {self.bits - 1}], "
                f"got {self.n_layers}")
        if self.n_layers > 1 and not self.dither:
            raise ValueError(
                "n_layers > 1 requires dither=True: deterministic rounding "
                "on a random layer is biased")

    @property
    def requires_key(self) -> bool:  # type: ignore[override]
        return bool(self.n_layers > 1 or self.dither)

    @property
    def epsilon_kind(self) -> str | None:  # type: ignore[override]
        return "gaussian_equiv" if self.requires_key else None

    def privacy_sigma(self) -> float:
        """Worst-case rounding-noise std in normalized log-domain units:
        layer j contributes Bernoulli variance <= (2^j / 2)^2 code units,
        averaged over the uniform layer draw."""
        if not self.requires_key:
            return 0.0
        var_codes = sum(4.0 ** j for j in range(self.n_layers)) / (
            4.0 * self.n_layers)
        return math.sqrt(var_codes) / self._cfg.levels

    def epsilon_per_use(self, delta: float = 1e-5) -> float:
        from repro.core.privacy.accounting import gaussian_epsilon
        return gaussian_epsilon(self.privacy_sigma(), delta)

    def codes(self, x, *, key=None):
        self._check_key(key)
        if key is None:
            return super().codes(x)
        x = x.astype(jnp.float32)
        kj, ku = jax.random.split(key)
        lv = self._cfg.levels
        q = log_compress(x, self.alpha) * lv
        if self.n_layers > 1:
            j = jax.random.randint(kj, x.shape, 0, self.n_layers)
            step = jnp.exp2(j.astype(jnp.float32))
        else:
            step = 1.0
        c = _value_unbiased_round(x, q, step, lv, self.alpha, ku)
        return jnp.clip(c, -lv, lv).astype(code_dtype(self.bits))

    def encode(self, x, *, key=None):
        self._check_key(key)
        if key is None:
            return super().encode(x)
        c = self.codes(x, key=key)
        if self.bits <= 4:
            return pack_nibbles(c)
        return c.reshape(-1)


def make_wire_codec(kind: str, *, bits: int = 8, alpha: float = 10.0,
                    backend: str = "jnp_ref") -> WireCodec:
    """Legacy shim over :func:`make_codec` for the original three kinds;
    new call sites should use ``make_codec`` directly."""
    if kind == "float32":
        return make_codec("float32")
    if kind == "log":
        return make_codec("log", bits=bits, alpha=alpha, backend=backend)
    if kind == "qsgd":
        return make_codec("qsgd", bits=bits, backend=backend)
    raise ValueError(f"unknown codec kind {kind!r}")


# --------------------------------------------------------------------------
# the shared collective phase
# --------------------------------------------------------------------------

def _local_absmax(x: jax.Array, stacked: bool) -> jax.Array:
    """Per-tensor max |x|; per-layer (leading dim) when stacked."""
    if stacked:
        return jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)), keepdims=True)
    return jnp.max(jnp.abs(x)).reshape(())


def codec_phase(xs: Sequence[jax.Array], stacked_flags: Sequence[bool],
                codec: WireCodec, comm: AxisComm | SymmetricWire,
                rec: CommRecord, *,
                avg_mode: str = "paper", wire: str = "allgather_codes",
                fuse: bool = False, keys: Sequence[jax.Array | None] | None = None,
                account_bits: Sequence[int] | None = None) -> list[jax.Array]:
    """Ship a list of tensors through one quantized collective phase.

    Every tensor is scaled against a globally-pmax'd per-instance grid
    (per-layer for stacked tensors), encoded by ``codec``, gathered —
    as ONE fused flat collective when ``fuse=True``, else one collective
    per tensor — then decoded and averaged:

      avg_mode='paper'             expand(mean(codes))   [Alg. 1 literal]
      avg_mode='dequant_then_mean' mean(expand(codes))

    ``wire='psum_sim'`` simulates the ring all-reduce with a pmean over
    (float) codes instead of gathering actual wire bytes.

    ``rec`` is charged the *actual* bits of each encoded wire array (packed
    b<=4 arrays are half their int8 size) plus 32 bits per scale, unless
    ``account_bits`` overrides the payload (TopK's sparse accounting over a
    dense simulation). Collective COUNTS include the scale sideband: a
    scale-bearing codec charges one pmax when ``fuse=True`` else one per
    tensor, on top of the gather/pmean collectives. Returns the
    synchronized (mean) tensors, one per input, in input shapes.

    Branch-safety: this function is pure in its traced values (the
    ``CommRecord`` mutations are Python-level, static accounting), so it is
    callable inside a ``lax.cond`` branch — the lazy-aggregation elision
    path (:mod:`repro.core.composite`) relies on this.
    """
    n = len(xs)
    if n == 0:
        return []
    keys = list(keys) if keys is not None else [None] * n
    xs = [x.astype(jnp.float32) for x in xs]
    # aggregation is the wire topology's call (plain mean on the symmetric
    # wire, participation/sparsity-weighted on the server wire); a bare
    # AxisComm lands on the symmetric path unchanged
    wt = as_wire(comm)

    # ---- shared quantization grid: per-instance global max ---------------
    # the codec's phases carry ENCODE (scale, quantise, pack) and DECODE
    # (unpack, dequantise, average) tags: HLO metadata for the trace's split
    if codec.needs_scale:
        with jax.named_scope(ENCODE_SCOPE):
            local = [_local_absmax(x, st) for x, st in zip(xs, stacked_flags)]
            if fuse:
                gmax = comm.fused_pmax(local)
            else:
                gmax = [comm.pmax(l) for l in local]
            safes = [jnp.where(s > 0, s, 1.0) for s in gmax]
            xn = [x / s for x, s in zip(xs, safes)]
        # the scale sideband is a real collective on the interconnect — one
        # fused pmax, or one per tensor — and is charged where it fires (its
        # BITS ride in codec.scale_bits with the payload accounting below)
        rec.add(0, 1 if fuse else n)
        n_scales = [s.size for s in safes]
    else:
        safes = [None] * n
        xn = xs
        n_scales = [0] * n

    def _rescale(val, safe):
        return val if safe is None else val * safe

    # ---- simulated ring all-reduce over codes ----------------------------
    if wire == "psum_sim":
        outs = []
        for i, (x, safe, key, ns) in enumerate(zip(xn, safes, keys, n_scales)):
            with jax.named_scope(ENCODE_SCOPE):
                c = codec.codes(x, key=key)
            # charge the PACKED container (codec.wire_bits), not x.size *
            # codec.bits: odd-length b<=4 tensors round up to a whole byte
            # on the real wire, so accounting agrees with 'allgather_codes'
            payload = (account_bits[i] if account_bits is not None
                       else codec.wire_bits(x.size))
            rec.add(payload + codec.scale_bits(ns), 1)
            with jax.named_scope(DECODE_SCOPE):
                if avg_mode == "paper":
                    val = codec.expand(wt.pmean(c.astype(jnp.float32)))
                else:
                    val = wt.pmean(codec.expand(c.astype(jnp.float32)))
                outs.append(_rescale(val, safe))
        return outs
    if wire != "allgather_codes":
        raise ValueError(f"unknown wire mode {wire!r}")

    # ---- exact wire: encode -> (fused) all-gather -> decode --------------
    with jax.named_scope(ENCODE_SCOPE):
        wires = [codec.encode(x, key=key) for x, key in zip(xn, keys)]
    for i, (w, ns) in enumerate(zip(wires, n_scales)):
        payload = (account_bits[i] if account_bits is not None
                   else w.size * w.dtype.itemsize * 8)
        rec.add(payload + codec.scale_bits(ns), 0)
    if fuse:
        gathered = comm.fused_all_gather(wires)
        rec.n_collectives += 1
    else:
        gathered = [comm.all_gather(w) for w in wires]
        rec.n_collectives += n

    outs = []
    for g, x, safe in zip(gathered, xs, safes):
        with jax.named_scope(DECODE_SCOPE):
            codes = codec.decode(g, x.size).reshape((g.shape[0],) + x.shape)
            if avg_mode == "paper":
                val = codec.expand(wt.average(codes))
            else:
                val = wt.average(codec.expand(codes))
            outs.append(_rescale(val, safe))
    return outs
