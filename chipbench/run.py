"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name from ``BENCHMARK.json``: its
configuration (``chipbench/configs/<config>.json``, with the plain
reference module it names under ``chipbench/reference/``), its traffic
(``chipbench/traffic/<traffic>.json``), the limits of its correctness check
(``chipbench/limits/<workload>.json``), one reader per per-layer metric
(``chipbench/metrics/<metric>.py``) and one FLOP counter per kind of layer
(``chipbench/counts/<kind>.py``). A reader's ``read(ctx)`` returns the
metric's value, or None where it finds nothing to read. ``ctx`` holds the
trace's reduction (``trace``), the parts of the model and compressor
buckets by their tags (``parts``, ``chipbench.splits``; ms per step), the
runtime spans' ms per step (``span_ms``, from ``AsyncRunner.span_s``), the
chip's peaks, the window's tokens/s and the counts of ``chipbench.flops``.

A run drives the program's own training path, as ``repro.launch.train``
assembles it: the step from ``build_sharded_step`` on a ``chips`` x 1
``data`` mesh, driven by ``AsyncRunner`` with prefetch. The state is made
from the seed by ``chipbench.weights``. Set-up runs the check's first steps
through that runner, then two more, which time one step; the window then
runs as many steps as fill ``--seconds`` by that time, in one call of the
runner, and ends when the device has finished them. With ``--trace 1`` the
window is cut to about two seconds (two steps at least), profiled, and the
per-layer metrics are read from its trace; otherwise the end-to-end metrics
are printed. Then the program's state is
freed and the reference replays the check's steps.

The last line of stdout is one JSON object; the compared numbers and their
limits are also the last lines of stderr. Without a TPU with enough chips
the run exits with status 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
WINDOW_SPAN = "chipbench.window"
TRACE_SECONDS = 2.0  # a traced window: about this long, and two steps at least
METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> dict:
    """The cell's entry, configuration, traffic, limits and metrics."""
    bench = _load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file}")
    cell = cells[workload]
    config = _load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = _load_json(BENCH / "limits" / f"{workload}.json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "limits": limits,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


STACK = ("lead", "pattern", "tail")


def _with_defaults(model: dict) -> dict:
    """The file's model with the program's defaults for the fields it does
    not give, at the top level and in each layer of its stack."""
    from repro.configs.base import LayerSpec, ModelConfig

    def defaults(cls) -> dict:
        return {
            f.name: f.default
            for f in dataclasses.fields(cls)
            if f.default is not dataclasses.MISSING
        }

    def as_dict(spec) -> dict:
        return dataclasses.asdict(spec) if isinstance(spec, LayerSpec) else spec

    layer = defaults(LayerSpec)
    out = {**defaults(ModelConfig), **model}
    for key in STACK:
        out[key] = [{**layer, **as_dict(spec)} for spec in out[key]]
    return json.loads(json.dumps(out))  # tuples -> lists, as in a file


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry for ``arch`` with every field the file gives, its layer stack
    (``lead``, ``pattern``, ``tail``) included, replaced by the file's.

    What the program then runs must be what the file says: every field the
    file gives, and the program's default for each it leaves out, so that
    a field the program gains later leaves older files as they were."""
    from repro.configs import get_config
    from repro.configs.base import LayerSpec

    model = config["model"]
    fields = {
        k: tuple(LayerSpec(**spec) for spec in v) if k in STACK else v
        for k, v in model.items()
    }
    cfg = dataclasses.replace(get_config(config["arch"]), **fields)
    cfg.validate()
    ran = json.loads(json.dumps(dataclasses.asdict(cfg)))
    want = _with_defaults(model)
    if ran != want:
        diff = sorted(k for k in set(ran) | set(want) if ran.get(k) != want.get(k))
        raise ValueError(f"config file and program disagree on {diff}")
    return cfg


class CompileCount:
    """Counts, while open, the programs JAX compiled or loaded from its
    persistent cache (``programs``) and those it had to compile because the
    cache missed (``misses``), from JAX's monitoring events."""

    PROGRAM = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.programs = 0
        self.misses = 0

    def _program(self, event: str, secs: float, **kw) -> None:
        if event == self.PROGRAM:
            self.programs += 1

    def _miss(self, event: str, **kw) -> None:
        if event == self.MISS:
            self.misses += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._program)
        jax.monitoring.register_event_listener(self._miss)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._program)
        jax.monitoring.unregister_event_listener(self._miss)

    def counts(self) -> dict[str, int]:
        return {"programs": self.programs, "cache_misses": self.misses}


def _peak_bytes(devices) -> int:
    """Peak HBM of the fullest chip: live arrays plus the memory the
    runtime reserved for the programs' temporaries (see PERF.md §2)."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0))
    return max(peaks)


class Cell:
    """One cell's program, built once: the model, compressor and optimizer
    from its files, the mesh, and the launcher's jitted step."""

    def __init__(self, spec: dict, devices, wrap_step=None):
        import jax

        from repro.core import CompressorConfig
        from repro.train.optimizer import make_optimizer
        from repro.train.runtime import build_sharded_step
        from repro.train.step import make_model_compressor

        self.spec = spec
        self.traffic = tr = spec["traffic"]
        self.model = spec["config"]["model"]
        self.init = spec["config"].get("init", {})  # leaf inits the file adds
        self.devices = devices
        self.chips = len(devices)
        self.cfg = model_config(spec["config"])
        comp = make_model_compressor(
            self.cfg,
            CompressorConfig(
                name=tr["compressor"],
                rank=tr["rank"],
                bits=tr["bits"],
                alpha=tr["alpha"],
                min_compress_numel=tr["min_compress_numel"],
            ),
        )
        opt = make_optimizer(tr["optimizer"], tr["lr"])
        self.rows = tr["batch_per_chip"] * self.chips
        self.mesh = jax.make_mesh(
            (self.chips, 1),
            ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
            devices=devices,
        )
        with jax.set_mesh(self.mesh):
            self.step, self.st_sh, _, self.st_abs = build_sharded_step(
                self.cfg,
                self.mesh,
                comp,
                opt,
                sample_batch=self.batch(0, 0),
                remat_scan=True,
            )
        if wrap_step is not None:
            self.step = wrap_step(self.step)

    def leaf_names(self) -> list[str]:
        import jax

        flat = jax.tree_util.tree_flatten_with_path(self.st_abs["params"])[0]
        return [jax.tree_util.keystr(p) for p, _ in flat]

    def batch(self, seed: int, step: int) -> dict:
        from chipbench import data

        tr = self.traffic
        return data.lm_batch(
            seed,
            step,
            vocab_size=self.model["vocab_size"],
            batch=self.rows,
            seq_len=tr["seq_len"],
            period=tr["period"],
            noise=tr["noise"],
        )

    def start(self, seed: int):
        """State from the seed and the runner over the step, driven through
        the check's first steps one call each, so that each step's loss is
        logged. Returns (runner, state, losses, snapshots, host copy s)."""
        import jax

        from chipbench import weights
        from repro.train.runtime import AsyncRunner, RuntimeConfig

        tr = self.traffic
        with jax.set_mesh(self.mesh):
            state = weights.make_state(seed, self.st_abs, self.st_sh, self.init)
            rcfg = RuntimeConfig(
                steps=0,
                log_every=tr["log_every"],
                prefetch=tr["prefetch"],
                verbose=False,
            )
            runner = AsyncRunner(self.step, lambda t: self.batch(seed, t), rcfg)
            snaps, copy_s = {}, 0.0
            k = tr["check_steps"]
            for t in range(k):
                rcfg.steps = t + 1
                state = runner.run(state, start_step=t)
                if t in (0, k - 1):
                    t0 = time.perf_counter()
                    snaps[t + 1] = jax.device_get(state["params"])
                    copy_s += time.perf_counter() - t0
        losses = [h["loss"] for h in runner.history]
        return runner, state, losses, snaps, copy_s

    def reference(self, seed: int, low=None) -> dict:
        """The reference's losses and per-leaf norms of w1 - w0 and wK - w0."""
        import jax

        from chipbench import weights
        from chipbench.reference.train import leaf_norms, reference_steps

        lm = _module(BENCH / "reference" / f"{self.spec['config']['reference']}.py")
        k = self.traffic["check_steps"]
        norms = {}
        with jax.default_matmul_precision("highest"):
            p0 = weights.init_params(seed, self.st_abs["params"], init=self.init)

            def on_step(t, tree):
                if t in (0, k - 1):
                    norms[t + 1] = leaf_norms(tree(), p0)

            losses = reference_steps(
                lm,
                self.model,
                p0,
                seed=seed,
                batches=[self.batch(seed, t) for t in range(k)],
                workers=self.chips,
                traffic=self.traffic,
                low=low,
                on_step=on_step,
            )
        return {"losses": losses, "step1": norms[1], "stepK": norms[k]}

    def program(self, seed: int, losses: list, snaps: dict) -> dict:
        """The program's losses and per-leaf norms, as ``reference`` gives."""
        import jax

        from chipbench import weights
        from chipbench.reference.train import leaf_norms

        k = self.traffic["check_steps"]
        with jax.default_matmul_precision("highest"):
            p0 = weights.init_params(seed, self.st_abs["params"], init=self.init)
            return {
                "losses": losses,
                "step1": leaf_norms(snaps[1], p0),
                "stepK": leaf_norms(snaps[k], p0),
            }


def run_cell(
    spec: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    require_tpu: bool = True,
    wrap_step=None,
    seen: dict | None = None,
) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``wrap_step`` lets a test put a broken step in the program's place; a
    traced run puts the compiled step's text and the trace in plain form
    into ``seen`` (``hlo``, ``trace``) where it is given."""
    import jax
    import numpy as np

    from chipbench import check, flops, peaks, splits

    chips = spec["cell"]["chips"]
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found {devices}")
    devices = devices[:chips]
    kind = devices[0].device_kind
    chip_peaks = peaks.peaks_for(kind) if require_tpu else None

    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        # cache the small programs too, so that a warm run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if trace:
        # the cache's key leaves source metadata out by default, so a traced
        # run could be given a step compiled by other source, other op_names
        jax.config.update(METADATA_IN_KEY, True)

    phases = {"imports": time.perf_counter() - t_start}
    with CompileCount() as setup_compiles:
        cell = Cell(spec, devices, wrap_step)
        phases["build"] = time.perf_counter() - t_start
        traffic, model = cell.traffic, cell.model
        runner, state, losses, snaps, copy_s = cell.start(seed)
        phases["check_steps"] = time.perf_counter() - t_start - copy_s
        rcfg = runner.cfg
        k_check = traffic["check_steps"]
        with jax.set_mesh(cell.mesh):
            # two more steps time one step of the window
            t0 = time.perf_counter()
            rcfg.steps = k_check + 2
            state = runner.run(state, start_step=k_check)
            step_s = (time.perf_counter() - t0) / 2
    setup_s = time.perf_counter() - t_start - copy_s
    phases["timing_steps"] = setup_s
    print(
        f"setup phases (s since start) {phases}; host copies {copy_s}; "
        f"programs {setup_compiles.counts()}",
        file=sys.stderr,
    )
    with jax.set_mesh(cell.mesh):
        n_steps = max(1, round(seconds / step_s))
        if trace:  # a profiled window is short: its trace is read in full
            n_steps = min(n_steps, max(2, round(TRACE_SECONDS / step_s)))
        first = rcfg.steps
        rcfg.steps = first + n_steps
        host_before, spans_before = runner.host_s, dict(runner.span_s)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        with CompileCount() as compiles:
            if trace:
                jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                state = runner.run(state, start_step=first)
                jax.block_until_ready(state)
            window_s = time.perf_counter() - t0
            if trace:
                jax.profiler.stop_trace()
        window_losses = [h["loss"] for h in runner.history[k_check:]]
        host_blocked_ms = 1e3 * (runner.host_s - host_before) / n_steps
        spans = span_ms(spans_before, runner.span_s, n_steps)
        wire_mb = runner.history[-1]["wire_mb_per_step"]
        peak = _peak_bytes(devices)
        hlo = None
        if trace:
            batch0 = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cell.batch(seed, 0)
            )
            abstract = st_abs_with(cell.st_abs, cell.st_sh)
            hlo = cell.step.lower(abstract, batch0).compile().as_text()
            # the reference's programs are the untraced runs' own
            jax.config.update(METADATA_IN_KEY, False)
        del state, runner
    gc.collect()
    jax.clear_caches()

    tokens = n_steps * cell.rows * traffic["seq_len"]
    result: dict = {
        "correct": False,
        "attempted": n_steps,
        "failed": 0,
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": kind,
            "count": chips,
            "memory_peak_bytes": peak,
        },
        "setup_programs": setup_compiles.counts(),
        "window_programs": compiles.counts(),
        "window_s": window_s,
    }
    if trace:
        from chipbench import trace as tr

        plain = tr.load_xplane(trace_dir, WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        scopes = tr.scope_map(hlo)
        reduced = tr.reduce_trace(plain, scopes, n_steps)
        parts = splits.split_trace(
            splits.op_ms(plain, scopes, n_steps), splits.sub_map(hlo)
        )
        if seen is not None:
            seen |= {"hlo": hlo, "trace": plain}
        del plain
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        flat = jax.tree_util.tree_flatten_with_path(cell.st_abs["params"])[0]
        leaves = [
            (tuple(a.shape), p[0] == jax.tree_util.DictKey("scan")) for p, a in flat
        ]
        c_bytes, c_flops = flops.compress_cost(
            leaves,
            rank=traffic["rank"],
            grad_bytes=np.dtype(cell.cfg.dtype).itemsize,
            err_bytes=_err_bytes(cell.st_abs),
            min_numel=traffic["min_compress_numel"],
        )
        ctx = {
            "trace": reduced,
            "peaks": chip_peaks,
            "chips": chips,
            "tokens_per_s": tokens / window_s,
            "model_flops_per_token": flops.model_flops_per_token(
                model, traffic["seq_len"]
            ),
            "compress_bytes": c_bytes,
            "compress_flops": c_flops,
            "host_blocked_ms": host_blocked_ms,
            "span_ms": spans,
            "parts": parts,
            "wire_mb_per_step": wire_mb,
        }
        for m in spec["per_layer"]:
            value = _module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        result["parts"] = parts
    else:
        e2e = {
            "tokens_per_s": (tokens / window_s, "tokens/s"),
            "peak_hbm_gb": (peak / 1e9, "GB"),
            "setup_s": (setup_s, "s"),
        }
        for m in spec["end_to_end"]:
            value, unit = e2e[m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": unit}

    # ---- correctness: the reference replays the check's steps
    t0 = time.perf_counter()
    prog, ref = cell.program(seed, losses, snaps), cell.reference(seed)
    values = check.readings(prog, ref, traffic["lr"])
    result["check_s"] = time.perf_counter() - t0
    losses = f"losses program {prog['losses']} reference {ref['losses']}"
    print(losses, file=sys.stderr)
    limits = spec["limits"]
    details = check.details(prog, ref, cell.leaf_names())
    details |= {k: v for k, v in values.items() if k not in limits}
    print(f"not compared {details}", file=sys.stderr)
    finite = all(math.isfinite(v) for v in window_losses)
    result["correct"] = finite and check.judge(values, limits)
    result["failed"] = 0 if finite else n_steps
    result["checks"] = {
        k: {"value": values[k], "limit": limits[k]} for k in sorted(limits)
    }
    return result


def span_ms(before: dict, after: dict, steps: int) -> dict[str, float]:
    """Milliseconds per step that each runtime span took between two
    readings of ``AsyncRunner.span_s`` (seconds by span name)."""
    return {k: 1e3 * (v - before.get(k, 0.0)) / steps for k, v in after.items()}


def st_abs_with(st_abs, st_sh):
    """The abstract state with its shardings, for lowering the step."""
    import jax

    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), st_abs, st_sh
    )


def _err_bytes(st_abs) -> int:
    errs = list(st_abs["comp"].get("err", {}).values())
    return errs[0].dtype.itemsize if errs else 4


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = load_cell(args.workload)
    try:
        result = run_cell(
            spec,
            seed=args.seed,
            seconds=args.seconds,
            trace=bool(args.trace),
            t_start=T_START,
        )
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
