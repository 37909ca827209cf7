"""What every job kind shares: the run's clock and records, the training session built
from a configuration file, the window, the comparison with the reference, and the one
result line.

Nothing here names a cell, a configuration, a model family, a traffic mix, a job kind
or a per-layer metric: those are files found by the names in ``BENCHMARK.json`` and in
the files it names (see README.md).
JAX is imported only inside functions, after :func:`Run.take_devices` has set the
compile cache's directory.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: steps the reference follows, and the program's first steps that are compared
COMPARED_STEPS = 3


class NoResult(SystemExit):
    """The run cannot produce a result (no TPU, too few chips, unknown device): exit
    non-zero and print no result line."""

    def __init__(self, why: str):
        print(f"benchmark: no result: {why}", file=sys.stderr)
        super().__init__(1)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_by_path(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py`` (names may hold dots and dashes, so
    it is loaded by path, not imported by name)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise NoResult(f"{name!r} has no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{abs(hash(name))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: what a family file has to expose (README.md, "A model family"); ``make_train_step``
#: takes ``optimizer=`` only in a family whose configurations state an ``optimizer`` key
FAMILY_CONTRACT = ("REFERENCE", "TINY", "program_config", "init_params", "make_train_step",
                   "param_specs", "train_flops_per_token")


def load_family(config: dict):
    """``benchmark/families/<family>.py`` of a configuration: all the benchmark knows of
    its architecture."""
    family = load_by_path("families", config["family"])
    missing = [name for name in FAMILY_CONTRACT if not hasattr(family, name)]
    if missing:
        raise NoResult(f"family {config['family']!r} lacks {missing} ({family.__file__})")
    return family


def load_reference(config: dict):
    """The family's plain reference, ``benchmark/reference/<name>.py``."""
    return load_by_path("reference", load_family(config).REFERENCE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, manifest: dict | None = None) -> Cell:
    manifest = manifest or read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise NoResult(f"BENCHMARK.json has no workload {name!r}; it has {sorted(cells)}")
    cell = cells[name]
    config_file = {c["name"]: c["file"] for c in manifest["configs"]}[cell["config"]]
    reported = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return Cell(
        name=name, chips=int(cell["chips"]), config_name=cell["config"],
        config=read_json(ROOT, config_file), traffic_name=cell["traffic"],
        traffic=read_json(HERE, "traffic", f"{cell['traffic']}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if reported(m)],
        per_layer=[m for m in manifest["per_layer"] if reported(m)],
    )


class CompileCounter:
    """Counts this process's compilations from JAX's own monitoring events: every
    compile that consulted the persistent cache, and every hit (copied from
    ``chip_smoke.py:CacheCounter``). A miss is a compile the backend really made."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "misses": self.requests - self.hits}


class Run:
    """One run of one cell: arguments, clock, the program's events, the steps, the
    window, what was attempted and what failed, and the result line."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_process: float, rehearsal: bool = False):
        self.cell, self.seed, self.seconds, self.trace = cell, int(seed), float(seconds), trace
        self.t_process = t_process
        self.rehearsal = rehearsal
        self.events: list[dict] = []
        self.steps: list[dict] = []  # {i, t0, t1, loss, incarnation}
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.excluded_s = 0.0  # the reference's time before the window: not set-up
        self.attempted = 0
        self.problems: list[str] = []  # one line per thing that broke a guarantee
        self.compared: dict[str, dict] = {}  # number compared -> its gap and its limit
        self.notes: dict = {}  # facts the job and the layer readers share
        self.reference: dict | None = None
        self.program: dict = {}  # what the program's first steps gave
        self.trace_result = None  # xplane.Reduced of the traced window
        self._trace_t0 = 0.0
        self._trace_dir: str | None = None
        self.compiles: CompileCounter | None = None
        self.compiles_at_open: dict | None = None
        self.compiles_in_window: dict | None = None
        self.device: dict = {}
        self.devices: list = []
        self.workdir = tempfile.mkdtemp(prefix="bench_")

    # -- start ------------------------------------------------------------------

    def take_devices(self) -> None:
        """Fix the compile cache's directory, take the chips, and refuse anything but
        the TPUs the cell asks for."""
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
        import jax

        from tpu_resiliency.platform.device import apply_compile_cache_env
        from tpu_resiliency.utils import events

        events.add_sink(lambda ev: self.events.append(ev.to_record()))
        self.compiles = CompileCounter()
        devices = jax.devices()
        dev = devices[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
        if not self.rehearsal:
            peaks = read_json(HERE, "peaks.json")
            if dev.platform != "tpu":
                raise NoResult(f"needs a TPU; JAX found platform {dev.platform!r} "
                               f"({dev.device_kind}, {len(devices)} device(s))")
            if dev.device_kind not in peaks["device_kinds"]:
                raise NoResult(f"device kind {dev.device_kind!r} is not in benchmark/peaks.json")
        if len(devices) < self.cell.chips:
            raise NoResult(f"cell {self.cell.name} needs {self.cell.chips} chip(s); "
                           f"JAX found {len(devices)}")
        self.devices = devices[: self.cell.chips]
        apply_compile_cache_env()
        self.phase("devices_taken")
        self.say("device", **self.device, cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"])

    def say(self, what: str, **facts) -> None:
        """A line of the run's log on stdout (never the last line)."""
        print(json.dumps({"log": what, **facts}, default=repr), flush=True)

    def phase(self, name: str) -> None:
        """Where set-up's seconds go: a log line with the time since process start."""
        self.say("phase", name=name, t=round(time.time() - self.t_process, 3))

    def problem(self, what: str) -> None:
        self.problems.append(what)
        self.say("problem", text=what)

    # -- the window ---------------------------------------------------------------

    @property
    def deadline(self) -> float:
        return self.t_open + self.seconds

    def open_window(self) -> None:
        self.t_open = time.time()
        self.compiles_at_open = self.compiles.snapshot()
        self.say("window_open", setup_s=self.setup_s, excluded_reference_s=self.excluded_s,
                 compiles_in_setup=self.compiles_at_open)

    def close_window(self) -> None:
        if self.t_close is not None:
            return
        self.t_close = time.time()
        now = self.compiles.snapshot()
        self.compiles_in_window = {k: now[k] - self.compiles_at_open[k] for k in now}
        self.stop_trace()
        self.say("window_closed", compiles_in_window=self.compiles_in_window)

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_process - self.excluded_s

    def in_window(self, t: float) -> bool:
        return self.t_open is not None and self.t_open <= t <= self.deadline

    def window_steps(self) -> list[dict]:
        """Steps completed inside the window, with the time since the previous
        completion (hooks included) as ``ms``."""
        out = []
        for prev, step in zip(self.steps, self.steps[1:]):
            if self.in_window(step["t1"]) and prev["t1"] >= self.t_open \
                    and prev["incarnation"] == step["incarnation"]:
                out.append({**step, "ms": (step["t1"] - prev["t1"]) * 1e3})
        return out

    def completed_in_window(self) -> int:
        return sum(1 for s in self.steps if self.in_window(s["t1"]) and s["t0"] >= self.t_open)

    # -- the traced window --------------------------------------------------------

    def maybe_start_trace(self) -> None:
        """In a traced run, open the profiler over the last ``trace_seconds`` of the
        window, at a step boundary."""
        if not self.trace or self._trace_dir is not None or self.t_open is None:
            return
        span = float(self.cell.traffic.get("trace_seconds", 5.0))
        if time.time() < self.deadline - min(span, self.seconds):
            return
        import jax

        self._trace_dir = os.path.join(self.workdir, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._trace_t0 = time.time()

    def stop_trace(self) -> None:
        if self._trace_dir is None or self.trace_result is not None:
            return
        import jax

        from . import xplane

        jax.block_until_ready(jax.numpy.zeros(()))
        t_stop = time.time()
        jax.profiler.stop_trace()
        try:
            self.trace_result = xplane.reduce_dir(self._trace_dir, window_s=t_stop - self._trace_t0)
        except RuntimeError as e:
            if not self.rehearsal:  # the CPU has no device plane; a TPU must
                raise
            self.say("trace", unread=str(e))
            return
        reduced = self.trace_result
        self.say("trace", window_s=reduced.window_s, busy_s=reduced.busy_s, planes=reduced.planes,
                 parse_s=time.time() - t_stop,
                 programs={k: [len(v), statistics.median(v)] for k, v in reduced.programs.items()},
                 kernels={k: [len(v), statistics.median(v)] for k, v in reduced.kernels().items()})

    def annotate(self, name: str):
        """A host span on the profiler's clock (``bench/<name>``), so that the trace
        reduction can say what the host was doing in an idle gap. Free when no trace
        is open."""
        import jax

        return jax.profiler.TraceAnnotation(f"bench/{name}")

    # -- the end --------------------------------------------------------------------

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def result(self, metrics: dict[str, float]) -> dict:
        units = {m["name"]: m["unit"] for m in self.cell.end_to_end + self.cell.per_layer}
        device = {**self.device, "memory_peak_bytes": self.memory_peak_bytes()}
        out = {
            "correct": not self.problems, "attempted": self.attempted,
            "failed": len(self.problems),
            "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
            "device": device,
        }
        if self.trace and self.trace_result is not None:
            device["busy_s"] = self.trace_result.busy_s
            device["window_s"] = self.trace_result.window_s
            out["breakdown"] = self.trace_result.breakdown()
        out["compared"] = self.compared  # last: what a record of a run that failed keeps
        return out

    def cleanup(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


# ---------------------------------------------------------------------------------
# the training session: model, step, state, feed, callbacks
# ---------------------------------------------------------------------------------

class Session:
    """The program under test, built from the cell's configuration file: the model
    family's jitted donating train step, its state on the device(s), and the batch
    feed. One per run; the jitted step is the one object that set-up warms and the
    window drives (a job that restarts in process re-jits it with :meth:`new_step`)."""

    def __init__(self, run: Run):
        import jax

        from tpu_resiliency.parallel import mesh as pmesh

        self.run = run
        c = run.cell.config
        self.batch, self.seq = c["batch"]
        self.family = load_family(c)
        self.cfg = self.family.program_config(c, self.seq)
        optimizer = c.get("optimizer")  # {"lr": <float>}; a file without it: AdamW at 3e-4
        if optimizer is not None and set(optimizer) != {"lr"}:
            raise NoResult(f"a configuration's optimizer states one number, lr; got {optimizer}")
        stated = {} if optimizer is None else {"optimizer": optimizer}
        self.train_step, self.init_opt = self.family.make_train_step(self.cfg, **stated)
        self.mesh = None
        axes = {k: int(v) for k, v in c.get("mesh", {}).items()}
        if axes:
            self.mesh = pmesh.build_mesh(devices=run.devices, **axes)
            self.param_shardings = pmesh.tree_shardings(
                self.mesh, self.family.param_specs(self.cfg))
            from jax.sharding import NamedSharding

            self.batch_sharding = NamedSharding(self.mesh, pmesh.batch_spec())
        self.step = None
        self._jax = jax

    def seeded_key(self):
        return self._jax.random.PRNGKey(self.run.seed % (1 << 32))

    def build_state(self):
        """Weights and AdamW state made on the device(s) from the seed, in one jitted
        call each, and the donating step."""
        jax = self._jax
        from tpu_resiliency.parallel import mesh as pmesh

        # the key is an argument: closed over, it would be a constant of the program,
        # and every seed would compile (and cache) a program of its own
        init = lambda key: self.family.init_params(key, self.cfg)  # noqa: E731
        if self.mesh is None:
            params = jax.jit(init)(self.seeded_key())
            opt_state = jax.jit(self.init_opt)(params)
            self.step = jax.jit(self.train_step, donate_argnums=(0, 1))
        else:
            params = jax.jit(init, out_shardings=self.param_shardings)(self.seeded_key())
            opt_shardings = pmesh.opt_state_shardings(
                self.init_opt, params, self.param_shardings)
            opt_state = jax.jit(self.init_opt, out_shardings=opt_shardings)(params)
            self.step = jax.jit(
                self.train_step, donate_argnums=(0, 1),
                out_shardings=(self.param_shardings, opt_shardings, None))
        jax.block_until_ready(opt_state)
        self.run.phase("state_built")
        return params, opt_state

    def new_step(self):
        """A fresh jit of the donating step for a state that is restored, not built."""
        self.step = self._jax.jit(self.train_step, donate_argnums=(0, 1))

    def tokens(self, i: int):
        """The batch of step ``i``: a function of seed and step, so a resume replays
        the same data order without saving a position."""
        host = self.host_tokens(i)
        if self.mesh is None:
            return self._jax.numpy.asarray(host)
        return self._jax.device_put(host, self.batch_sharding)

    def host_tokens(self, i: int):
        """Ids below the configuration file's ``vocab_size``: of a sliced vocabulary,
        the slice."""
        import numpy as np

        return np.random.default_rng([self.run.seed, i]).integers(
            0, self.run.cell.config["vocab_size"], (self.batch, self.seq)).astype(np.int32)

    def state_bytes(self, state) -> int:
        return sum(x.size * x.dtype.itemsize for x in self._jax.tree.leaves(state))

    # -- the numbers the reference is compared with --------------------------------

    def first_gradient_norms(self, opt_state) -> dict[str, float]:
        """Norm of every leaf of the first gradient as the optimizer got it: AdamW's
        first moment after one step is (1 - b1) times it."""
        from .reference.train import B1, leaf_norms

        return {k: v / (1 - B1) for k, v in leaf_norms(opt_state[0].mu).items()}

    def change_norms(self, params) -> dict[str, float]:
        """Norm of every parameter leaf's change against the seeded weights, which
        are made again inside the program (the state fills the chip; no copy is
        kept)."""
        jax = self._jax
        jnp = jax.numpy

        def norms(p, key):
            p0 = self.family.init_params(key, self.cfg)
            return jax.tree.map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0)

        out = jax.jit(norms)(params, self.seeded_key())
        flat = jax.tree_util.tree_flatten_with_path(out)[0]
        return {jax.tree_util.keystr(path): float(v) for path, v in flat}


class StepDriver:
    """The ``step_fn`` that ``integrations.run_training`` drives, in set-up and in the
    window alike: feed, the donating step, the loss read back, the record. It opens
    the window when the job's warm-up is done, closes it at the deadline, and asks
    the loop to stop after ``tail_steps`` more."""

    def __init__(self, run: Run, session: Session, ctx, incarnation: int = 0,
                 ready=lambda i: True, on_completed=None, tail_steps: int = 0):
        self.run, self.session, self.ctx = run, session, ctx
        self.incarnation = incarnation
        self.ready = ready  # ready(i): set-up is done before step i
        self.on_completed = on_completed  # called with the step index after each step
        self.tail_left = tail_steps
        self._hooks = None
        self._stepped = False

    def __call__(self, state, i: int):
        run = self.run
        if self._hooks is not None:
            self._hooks.__exit__(None, None, None)
            self._hooks = None
        if run.t_open is None and self.ready(i):
            run.open_window()
        run.maybe_start_trace()
        t0 = time.time()
        with run.annotate("feed"):
            tokens = self.session.tokens(i)
        first_after_reentry = self.incarnation > 0 and not self._stepped
        self._stepped = True
        with run.annotate("step_after_reentry" if first_after_reentry else "step"):
            params, opt_state, loss = self.session.step(*state, tokens)
            del state
            loss = float(loss)  # the host needs it: waits for the step
        t1 = time.time()
        run.steps.append({"i": i, "t0": t0, "t1": t1, "loss": loss,
                          "incarnation": self.incarnation})
        run.attempted += 1
        if not (loss == loss and abs(loss) != float("inf")):
            run.problem(f"step {i}: loss {loss} is not finite")
        if i < COMPARED_STEPS or run.t_open is None and i % 8 == 0:
            run.phase(f"step_{i}_done")
        if self.incarnation == 0 and i < COMPARED_STEPS:
            run.program.setdefault("losses", []).append(loss)
            if i == 0:
                run.program["grad_norms"] = self.session.first_gradient_norms(opt_state)
            if i == COMPARED_STEPS - 1:
                run.program["change_norms"] = self.session.change_norms(params)
        if run.t_open is not None and t1 >= run.deadline:
            run.close_window()
            if self.tail_left <= 0:
                self.ctx.should_stop = True
            self.tail_left -= 1
        if self.on_completed is not None:
            self.on_completed(i)
        self._hooks = run.annotate("hooks")
        self._hooks.__enter__()
        return params, opt_state

    def finish(self) -> None:
        if self._hooks is not None:
            self._hooks.__exit__(None, None, None)
            self._hooks = None


def straggler_callback(run: Run):
    """The telemetry callback as ``chip_smoke.py:run_incarnation`` wires it. A traced
    run gives it no profiler windows of its own: one process holds one window at a
    time, and the traced run's is the harness's."""
    from tpu_resiliency.integrations import StragglerDetectionCallback

    every = 0 if run.trace else int(run.cell.traffic.get("profile_programs_every", 3))
    return StragglerDetectionCallback(
        report_time_interval=0.0, use_device_mesh=True, use_pallas=True,
        profile_programs_every=every, mesh_signal_capacity=64,
    )


# ---------------------------------------------------------------------------------
# correct: the reference, and the read-back of a checkpoint
# ---------------------------------------------------------------------------------

def follow_reference(run: Run, session: Session, precision: str = "f32") -> dict:
    """The reference's first steps on the seed's weights and batches. Runs before the
    program's state is made; its seconds are not set-up."""
    from .reference import train

    t0 = time.time()
    batches = [session.host_tokens(i) for i in range(COMPARED_STEPS)]
    out = train.follow(run.seed % (1 << 32), run.cell.config, batches, precision)
    out["seconds"] = time.time() - t0
    if run.t_open is None:
        run.excluded_s += out["seconds"]
    run.phase("reference_followed")
    return out


def worst_gap(program: dict[str, float], reference: dict[str, float]) -> tuple[float, str]:
    """The worst leaf by |program's norm - reference's norm| over the larger of the
    reference's norm of that leaf and of its median leaf (some gradients are all but
    zero)."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        gap = abs(program.get(leaf, 0.0) - ref) / max(ref, floor, 1e-30)
        if not gap <= worst:  # a NaN wins
            worst, where = gap, leaf
    return worst, where


def compare_with_reference(run: Run, program: dict, reference: dict, limits: dict) -> list[dict]:
    """Every number compared, beside its limit. A number over its limit, or missing,
    is a problem of the run."""
    rows = []
    losses = program.get("losses", [])
    for i, ref in enumerate(reference["losses"]):
        got = losses[i] if i < len(losses) else float("nan")
        rows.append({"number": f"loss_step{i}", "program": got, "reference": ref,
                     "gap": abs(got - ref), "limit": limits["loss_abs"]})
    for key, limit in (("grad_norms", "grad_norm_gap"), ("change_norms", "change_norm_gap")):
        gap, leaf = worst_gap(program.get(key, {}), reference[key])
        rows.append({"number": f"{key}_worst_leaf", "leaf": leaf,
                     "program": program.get(key, {}).get(leaf), "reference": reference[key].get(leaf),
                     "gap": gap, "limit": limits[limit]})
    for row in rows:
        row["ok"] = bool(row["gap"] <= row["limit"])
        run.say("compare", **row)
        run.compared[row["number"]] = {
            "gap": row["gap"] if math.isfinite(row["gap"]) else None, "limit": row["limit"]}
        run.attempted += 1
        if not row["ok"]:
            run.problem(f"{row['number']}: gap {row['gap']:.6g} over its limit {row['limit']}")
    return rows


def device_leaf_crcs(tree) -> list[int]:
    """CRC of every array leaf's bytes as the device holds them, in the order a
    container stores them (copied from ``chip_smoke.py``)."""
    import jax
    import numpy as np

    from tpu_resiliency.checkpoint import format as ckpt_format

    return [
        ckpt_format.crc32c(memoryview(np.ascontiguousarray(np.asarray(x))).cast("B"))
        for x in jax.tree.leaves(tree)
    ]


def container_leaf_crcs(ckpt_dir: str, rank: int, iteration: int) -> tuple[list[int], int]:
    """(copied from ``chip_smoke.py``)"""
    from tpu_resiliency.checkpoint import format as ckpt_format
    from tpu_resiliency.checkpoint.local_manager import CkptID

    path = os.path.join(ckpt_dir, "s0", f"r{rank}", CkptID(iteration, rank).filename())
    _, _, info = ckpt_format.read_trailer(path)
    return list(info.leaf_crcs), os.path.getsize(path)


def checkpoint_callback(manager, local_every: int):
    from tpu_resiliency.integrations import HierarchicalCheckpointCallback

    return HierarchicalCheckpointCallback(
        local_manager=manager, local_every=local_every, driven_by_loop=True,
        to_state_dict=lambda st: {"params": st[0], "opt": st[1]},
        from_state_dict=lambda st, loaded: (loaded["params"], loaded["opt"]),
    )


def restore(run: Run, ckpt_cb, ctx) -> dict:
    """``restore_latest`` to ``block_until_ready``; the caller has freed the state."""
    import jax

    t0 = time.time()
    with run.annotate("restore"):
        if not ckpt_cb.restore_latest(ctx):
            run.problem("restore_latest found no checkpoint")
            return {"restore_s": time.time() - t0, "step": None}
        jax.block_until_ready(ctx.state)
    return {"restore_s": time.time() - t0, "step": ctx.start_step}


def verify_restored(run: Run, session: Session, ckpt_dir: str, ctx, replay: int) -> None:
    """The restored leaves are byte-equal to the container's trailer, and the next
    steps repeat the losses the first pass recorded at those steps (same seed, same
    program, same chip: exact)."""
    state = ctx.state
    want, file_bytes = container_leaf_crcs(ckpt_dir, 0, ctx.start_step)
    got = device_leaf_crcs({"params": state[0], "opt": state[1]})
    run.attempted += 1
    run.say("restored", step=ctx.start_step, leaves=len(got), crc_equal=got == want,
            file_bytes=file_bytes, state_bytes=session.state_bytes(state))
    if got != want:
        bad = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
        run.problem(f"restored leaves differ from the container's CRCs at {bad}")
    first = first_pass_losses(run)
    for i in range(ctx.start_step, ctx.start_step + replay):
        tokens = session.tokens(i)
        params, opt_state, loss = session.step(*state, tokens)
        state = (params, opt_state)
        loss = float(loss)
        run.attempted += 1
        run.say("replay", step=i, loss=loss, first_pass=first.get(i), limit=0.0)
        if i not in first or loss != first[i]:
            run.problem(f"replayed step {i}: loss {loss} != first pass {first.get(i)}")
    ctx.state = state


def first_pass_losses(run: Run) -> dict[int, float]:
    """{step: the loss the first incarnation that ran it recorded}."""
    first: dict[int, float] = {}
    for s in run.steps:
        first.setdefault(s["i"], s["loss"])
    return first


def median(values) -> float | None:
    values = list(values)
    return statistics.median(values) if values else None


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile."""
    values = sorted(values)
    if not values:
        return None
    k = max(0, min(len(values) - 1, int(-(-q * len(values) // 1)) - 1))
    return values[k]


def window_events(run: Run, kind: str, **match) -> list[dict]:
    """The program's events of one kind recorded inside the window."""
    return [e for e in run.events
            if e.get("kind") == kind and run.in_window(e["ts"])
            and all(e.get(k) == v for k, v in match.items())]


def saves(run: Run) -> list[dict]:
    """One record per save the program began: when, and the seconds each engine kept
    the loop blocked. A ``detach`` record belongs to the save requested before it
    (one is outstanding at a time)."""
    out: list[dict] = []
    for e in run.events:
        if e.get("kind") != "ckpt_foreground_blocked":
            continue
        if e.get("engine") == "pipelined":
            out.append({"iteration": e.get("iteration"), "ts": e["ts"],
                        "enqueue_s": e["duration_s"], "detach_s": 0.0})
        elif e.get("engine") == "detach" and out:
            out[-1]["detach_s"] += e["duration_s"]
    return out


#: every process of this run inherits this variable: how a detached daemon of the
#: program (the in-process wrapper's monitor) is found and waited for
RUN_TOKEN_ENV = "BENCHMARK_RUN_TOKEN"


def wait_for_descendants(grace_s: float = 15.0) -> list[int]:
    """Wait until every other process carrying this run's token has ended; kill what
    is left after ``grace_s``. Returns the pids that had to be killed."""
    import signal

    mark = f"{RUN_TOKEN_ENV}={os.environ.get(RUN_TOKEN_ENV, '')}".encode()

    def alive() -> list[int]:
        pids = []
        for entry in os.listdir("/proc"):
            if not entry.isdigit() or int(entry) == os.getpid():
                continue
            try:
                with open(f"/proc/{entry}/environ", "rb") as f:
                    if mark in f.read().split(b"\0"):
                        with open(f"/proc/{entry}/stat") as s:
                            if s.read().rsplit(")", 1)[1].split()[0] != "Z":
                                pids.append(int(entry))
            except OSError:
                continue
        return pids

    deadline = time.time() + grace_s
    while alive() and time.time() < deadline:
        time.sleep(0.2)
    left = alive()
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    return left
