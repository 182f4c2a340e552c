"""Asynchronous production training runtime.

The reference :class:`~repro.train.trainer.Trainer` is a bare synchronous
loop: build a batch, dispatch the step, ``float()`` every logged metric —
so the host blocks device dispatch once per logged step, and checkpoints
``device_get`` the full state on the hot path. PowerSGD's own evaluation
(Vogels et al., 2019) is explicit that gradient compression only pays off
in end-to-end *wall-clock*; this module makes the loop itself production
shaped:

  * **Sharded birth** — :func:`sharded_init` jits state construction with
    ``out_shardings``, so params/opt/compressor state materialize directly
    on the mesh instead of on host followed by a transfer.
  * **Explicitly sharded step** — :func:`build_sharded_step` jits the
    train step with the ``in_shardings``/``out_shardings`` derived by
    ``build_train_step`` plus buffer donation. (The launcher used to drop
    these shardings on the floor: under default placement the per-worker
    error feedback replicated over the ``model`` axis — the exact failure
    mode ``train/step.py`` documents as fatal at 70B+ scale.)
  * **Prefetching input pipeline** — a background thread builds batch N+1
    while step N runs; the step's ``in_shardings`` place it onto the batch
    shardings at dispatch.
  * **Non-blocking metrics** — logged metrics stay device arrays and are
    fetched one log-interval late, when the device has already moved on;
    only the final interval truly syncs.
  * **Background checkpointing** — a donated-safe device-side copy goes to
    :class:`repro.checkpoint.io.AsyncCheckpointer`; the hot loop never
    waits on ``device_get`` + serialization.
  * **Gradient accumulation** — ``microbatch=k`` threads through to
    ``build_train_step(accum_steps=k)``: k sequential microbatches per
    step, with the compressed sync firing once per *accumulated* step,
    exactly where the paper's Algorithm 1 places the quantized collective.

:func:`run_schedule` drives ONE runner through the compression schedule's
phases (end of warm-up + every decay boundary): history and wall-clock
survive boundaries, and a restored checkpoint skips phases it already
completed, so warm-Q truncations are never re-applied to state past them.

``AsyncRunner`` changes *when the host blocks*, never the math: it is
bit-for-bit equal to ``Trainer`` on the same jitted step (tested), and
``benchmarks/step_time.py`` tracks the wall-clock delta as a first-class
regression quantity (``BENCH_step_time.json``).

Host spans: each phase of the loop (``SPANS``) is a
``jax.profiler.TraceAnnotation`` — on the profiler's clock, beside the
device's ops, when a trace is being taken — and ``time.perf_counter``
seconds summed into ``AsyncRunner.span_s[name]``. ``host_s`` is the sum of
the ``BLOCKING_SPANS``, the time the main thread waits.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import queue
import sys
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import AsyncCheckpointer
from repro.train.step import build_train_step, init_train_state, n_dp_of
from repro.train.trainer import TrainerConfig

__all__ = ["RuntimeConfig", "AsyncRunner", "build_sharded_step",
           "sharded_init", "run_schedule", "SPANS", "BLOCKING_SPANS"]

PyTree = Any

PREFETCH_WAIT = "runtime.prefetch_wait"  # main thread waits for a batch
DISPATCH = "runtime.dispatch"            # the step_fn call (enqueues the step)
METRICS_FETCH = "runtime.metrics_fetch"  # an earlier interval's metrics
DRAIN = "runtime.drain"                  # run's end: last metrics, ckpt writes
CKPT_SUBMIT = "runtime.ckpt_submit"      # snapshot dispatch for a checkpoint
BATCH_BUILD = "runtime.batch_build"      # prefetch thread: batch_fn(i)
SPANS = (PREFETCH_WAIT, DISPATCH, METRICS_FETCH, DRAIN, CKPT_SUBMIT,
         BATCH_BUILD)
BLOCKING_SPANS = (PREFETCH_WAIT, METRICS_FETCH, DRAIN, CKPT_SUBMIT)


@dataclasses.dataclass
class RuntimeConfig(TrainerConfig):
    microbatch: int = 1   # gradient-accumulation factor (1 = off)
    prefetch: int = 2     # device batches kept in flight ahead of dispatch


def build_sharded_step(cfg, mesh, compressor, optimizer, *, sample_batch,
                       microbatch: int = 1, **build_kwargs):
    """The launcher's step: ``build_train_step`` jitted WITH its derived
    shardings and donation.

    Returns ``(jitted_step, state_shardings, batch_shardings,
    state_abstract)``. ``sample_batch`` (one ``batch_fn`` output) fixes the
    batch pytree/shapes the step is specialized to.
    """
    step_fn, state_sh_fn, batch_sh_fn = build_train_step(
        cfg, mesh, compressor, optimizer, accum_steps=microbatch,
        **build_kwargs)
    state_abs = jax.eval_shape(
        lambda k: init_train_state(cfg, k, optimizer, compressor,
                                   n_dp_of(mesh)),
        jax.random.PRNGKey(0))
    st_sh = state_sh_fn(state_abs)
    batch_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sample_batch)
    b_sh = batch_sh_fn(batch_abs)
    jstep = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                    out_shardings=(st_sh, None), donate_argnums=0)
    return jstep, st_sh, b_sh, state_abs


def sharded_init(cfg, key: jax.Array, optimizer, compressor, mesh,
                 state_shardings) -> dict:
    """Initialize the train state born on the mesh: the whole init is one
    jit with ``out_shardings``, so XLA materializes each leaf directly into
    its placement (no full host-side state + transfer)."""
    init = jax.jit(
        lambda k: init_train_state(cfg, k, optimizer, compressor,
                                   n_dp_of(mesh)),
        out_shardings=state_shardings)
    return init(key)


class _Prefetcher:
    """Host-side input pipeline: a daemon thread runs ``batch_fn(i)`` for
    upcoming steps while the main thread's (GIL-releasing) step execution
    runs. Bounded queue => bounded memory for staged batches.

    The device transfer itself is NOT issued from this thread: the jitted
    step's ``in_shardings`` place each host batch onto the batch shardings
    at dispatch. Issuing ``device_put`` from a secondary thread serializes
    against the in-flight step's execution on the runtime's dispatch locks
    (measured 3-4x WORSE than the synchronous loop on CPU), and an extra
    main-thread ``device_put`` just duplicates what the jit call does."""

    def __init__(self, batch_fn: Callable[[int], Any], start: int, stop: int,
                 depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._err: BaseException | None = None

        def work() -> None:
            try:
                for i in range(start, stop):
                    if self._stop.is_set():
                        return
                    b = batch_fn(i)
                    while not self._stop.is_set():
                        try:
                            self._q.put(b, timeout=0.1)
                            break
                        except queue.Full:
                            continue
            except BaseException as e:
                self._err = e

        self._thread = threading.Thread(target=work, name="batch-prefetch",
                                        daemon=True)
        self._thread.start()

    def get(self) -> Any:
        while True:
            try:
                return self._q.get(timeout=0.5)
            except queue.Empty:
                if self._err is not None:
                    raise RuntimeError("batch prefetch failed") from self._err
                if not self._thread.is_alive():
                    raise RuntimeError(
                        "batch prefetch thread exited without producing the "
                        "requested batch")

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


class _SnapshotPacker:
    """Donated-safe state snapshots for background checkpointing, one
    jitted dispatch per snapshot (an eager per-leaf ``jnp.copy`` costs one
    dispatch per leaf — ~80x slower on CPU).

    Single-device mesh: leaves are additionally concatenated into ONE flat
    buffer per dtype, so the writer thread pulls a handful of transfers
    instead of one per leaf (per-leaf ``device_get`` from a background
    thread contends with the in-flight step on the runtime's client
    locks — the regime the throughput benchmark measures).

    Multi-device mesh: the copy PRESERVES each leaf's sharding and the
    writer assembles shards on the host. Packing would force every leaf
    replicated first, transiently materializing the full fp32 state per
    device — the exact memory blow-up the sharded runtime exists to avoid
    at 70B+ scale. (It also dodges a GSPMD quirk: a mixed-sharding concat
    left to GSPMD partial-SUMS over the model axis — a step counter of 3
    read back as 6 on a 4x2 mesh, regression-tested.)"""

    def __init__(self, state: PyTree):
        leaves, self._treedef = jax.tree_util.tree_flatten(state)
        self._shapes = [tuple(x.shape) for x in leaves]
        self._groups: dict[str, list[int]] = {}
        for i, x in enumerate(leaves):
            self._groups.setdefault(str(x.dtype), []).append(i)
        mesh = getattr(getattr(leaves[0], "sharding", None), "mesh", None)
        self._packed = mesh is None or math.prod(mesh.shape.values()) == 1

        def pack(s: PyTree) -> dict[str, jax.Array]:
            ls = jax.tree_util.tree_flatten(s)[0]
            return {dt: jnp.concatenate([ls[i].reshape(-1) for i in idxs])
                    for dt, idxs in self._groups.items()}

        def copy(s: PyTree) -> PyTree:
            return jax.tree.map(jnp.copy, s)

        self._pack = jax.jit(pack if self._packed else copy)

    def snapshot(self, state: PyTree) -> Callable[[], PyTree]:
        """Dispatch the device-side copy NOW (before the caller's next step
        donates ``state``); return a thunk the writer thread calls to
        materialize the host pytree."""
        packed = self._pack(state)
        if not self._packed:
            return lambda: jax.device_get(packed)

        def materialize() -> PyTree:
            host = {dt: np.asarray(v) for dt, v in packed.items()}
            out: list[Any] = [None] * len(self._shapes)
            for dt, idxs in self._groups.items():
                flat, off = host[dt], 0
                for i in idxs:
                    n = math.prod(self._shapes[i])
                    out[i] = flat[off:off + n].reshape(self._shapes[i])
                    off += n
            return jax.tree_util.tree_unflatten(self._treedef, out)

        return materialize


# packers are cached on the state's (structure, shapes, dtypes, mesh)
# signature: the jitted pack graph would otherwise recompile for every
# runner/run (each `jax.jit` call site owns its own compile cache)
_PACKER_CACHE: dict[Any, _SnapshotPacker] = {}


def _packer_for(state: PyTree) -> _SnapshotPacker:
    leaves, treedef = jax.tree_util.tree_flatten(state)
    key = (treedef,
           tuple((tuple(x.shape), str(x.dtype)) for x in leaves),
           getattr(getattr(leaves[0], "sharding", None), "mesh", None))
    packer = _PACKER_CACHE.get(key)
    if packer is None:
        if len(_PACKER_CACHE) > 16:   # phases/models churn: stay bounded
            _PACKER_CACHE.clear()
        packer = _PACKER_CACHE[key] = _SnapshotPacker(state)
    return packer


class AsyncRunner:
    """Drop-in :class:`Trainer` replacement with the async behaviors (see
    module docstring). Same ``run(state, start_step=None)`` contract,
    ``history`` schema, resume-from-``state['step']`` semantics, and
    save-on-interval-and-final-step checkpoint grid."""

    def __init__(self, step_fn: Callable, batch_fn: Callable[[int], Any],
                 cfg: RuntimeConfig):
        self.step_fn = step_fn
        self.batch_fn = batch_fn
        self.cfg = cfg
        self.history: list[dict[str, float]] = []
        # seconds per host span, summed over runs (the prefetch thread
        # adds batch_build, so updates take the lock)
        self.span_s: dict[str, float] = dict.fromkeys(SPANS, 0.0)
        self._span_lock = threading.Lock()
        self._t0: float | None = None

    @property
    def host_s(self) -> float:
        """Main-thread blocked time (cf. ``Trainer.host_s``): the blocking
        spans' sum; dispatch and the prefetch thread are not in it."""
        return sum(self.span_s[name] for name in BLOCKING_SPANS)

    @contextlib.contextmanager
    def _span(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._span_lock:
                self.span_s[name] += dt

    def _build_batch(self, i: int) -> Any:
        with self._span(BATCH_BUILD):
            return self.batch_fn(i)

    def _emit(self, step: int, metrics: Any) -> None:
        # ONE transfer for the whole metric dict — per-metric float() pays
        # a separate host sync per value (the sync loop's behavior)
        m = {k: float(v) for k, v in jax.device_get(metrics).items()}
        m["step"] = step
        # as in Trainer: when the step's metrics reached the host, so the
        # step had finished (at log_every=1 that is its completion time)
        m["wall_s"] = round(time.time() - self._t0, 2)
        self.history.append(m)
        if self.cfg.verbose:
            msg = " ".join(f"{k}={v:.4f}" for k, v in m.items()
                           if k not in ("step", "wall_s"))
            print(f"step {step:5d} | {msg} | t={m['wall_s']}s")

    def run(self, state: Any, start_step: int | None = None) -> Any:
        if start_step is None:
            start_step = (int(jax.device_get(state["step"]))
                          if isinstance(state, dict) and "step" in state
                          else 0)
        if self._t0 is None:
            self._t0 = time.time()
        cfg = self.cfg
        saver = AsyncCheckpointer(cfg.ckpt_path) if cfg.ckpt_every else None
        pf = _Prefetcher(self._build_batch, start_step, cfg.steps,
                         depth=cfg.prefetch)
        pending: list[tuple[int, Any]] = []
        # the jitted step makes many brief GIL round-trips while it blocks;
        # with background threads active, each re-acquire can wait a full
        # interpreter switch interval (5ms default) — shrink it for the
        # duration of the run so handoffs cost ~us, not ms
        prev_switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)
        try:
            for step in range(start_step, cfg.steps):
                with self._span(PREFETCH_WAIT):
                    batch = pf.get()
                with self._span(DISPATCH):
                    state, metrics = self.step_fn(state, batch)
                if (step % cfg.log_every == 0
                        or step == cfg.steps - 1):
                    pending.append((step, metrics))
                # fetch only the PREVIOUS interval's metrics: this step is
                # already queued on the device, so the float() sync below
                # overlaps compute instead of stalling dispatch
                while len(pending) > 1:
                    with self._span(METRICS_FETCH):
                        self._emit(*pending.pop(0))
                if saver and (step == cfg.steps - 1
                              or (step and step % cfg.ckpt_every == 0)):
                    # device-side packed copy: dispatched before the next
                    # step donates `state`, so the writer thread reads a
                    # stable snapshot while training runs ahead
                    with self._span(CKPT_SUBMIT):
                        saver.submit(_packer_for(state).snapshot(state))
            with self._span(DRAIN):
                while pending:
                    self._emit(*pending.pop(0))
                if saver:
                    saver.drain()   # surface background write errors
        finally:
            sys.setswitchinterval(prev_switch)
            pf.close()
            if saver:
                saver.close()
        return state


def run_schedule(runner, compressor, state, *, total_steps: int,
                 rebuild: Callable, initial=None):
    """Drive ``runner`` through the compression schedule's phases.

    ``rebuild(comp_t, seg_start) -> (jitted_step, state_shardings | None)``
    is invoked only for phases whose compressor differs from the one
    currently in force; the adapted state is resharded onto the returned
    shardings. ``initial`` names the compressor the runner's current
    ``step_fn`` was built for (defaults to ``compressor``) — pass the
    ``at_step(resume)`` compressor when resuming a restored checkpoint.

    Two launcher bugs this replaces (both regression-tested):

      * one ``Trainer`` per phase discarded ``history`` and restarted the
        wall-clock at every boundary — here ONE runner threads through;
      * the phase loop always started at segment 0 and re-applied
        ``adapt_state`` (warm-Q truncation) for boundaries a restored
        checkpoint was already past — here phases with
        ``seg_end <= state['step']`` are skipped outright.
    """
    sched = getattr(compressor, "schedule", None)
    bounds = ([b for b in sched.boundaries() if 0 < b < total_steps]
              if sched is not None else [])
    resume = (int(jax.device_get(state["step"]))
              if isinstance(state, dict) and "step" in state else 0)
    comp_prev = initial if initial is not None else compressor
    for seg_start, seg_end in zip([0] + bounds, bounds + [total_steps]):
        if seg_end <= resume:
            continue   # phase fully behind the restored step: never re-adapt
        at = getattr(comp_prev, "at_step", None)
        comp_t = at(max(seg_start, resume)) if at is not None else comp_prev
        if comp_t is not comp_prev:
            state = dict(state)
            state["comp"] = comp_t.adapt_state(state["comp"])
            runner.step_fn, st_sh = rebuild(comp_t, seg_start)
            if st_sh is not None:
                state = jax.device_put(state, st_sh)
            comp_prev = comp_t
        runner.cfg.steps = seg_end
        state = runner.run(state)
    return state
