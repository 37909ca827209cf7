#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the resilient-training main path once, through the entry points a user
calls, and checks what comes out by the repo's own means. One chip, no arguments
(what the driver runs)::

    python chip_smoke.py

- phase ``telemetry``: ``MeshTelemetry`` at 4096 ranks x 64 signals x 32 window on
  a one-device mesh — pushes from inside a jitted, donated step, one scoring
  round with the Pallas kernel, medians against numpy, straggler F1 against
  ``make_telemetry``'s truth, program times from the profiler's device plane;
- phase ``train``: ``tpu-ft-launcher`` -> this file as the worker ->
  ``integrations.run_training`` + FT / straggler / hierarchical-checkpoint
  callbacks over a ``LocalCheckpointManager``, at the full width and depth of the
  flagship model (8L x 1024d, batch 8 x seq 1024, AdamW: 1.9 GB of state). Round 0
  saves asynchronously and SIGKILLs itself; round 1 (a promoted warm spare) takes
  the chip, restores, proves the restored leaves byte-equal to the container's
  CRCs, and finishes;
- phase ``inprocess``: ``inprocess.Wrapper`` around the same train function in
  one process: an injected exception, the abort ladder, re-entry, restore, more
  steps — without losing the chip or running out of HBM.

``--chips 4`` (run by the builder, never by the driver) runs ONLY the path that
exists across chips, and what it is compared with: the launcher starts one worker
that drives all four chips — flagship step on a dp x tp mesh, ``MeshTelemetry``
sharded four ways with the Pallas reduction inside ``shard_map``, async save and
restore of the sharded state — against the same seeds on one device of the same
process. ``--tiny`` rehearses every phase's control flow on whatever backend JAX
finds (the CPU tests use it); with it, or off a TPU, the ``"ok": true`` line is
never printed.

This parent process NEVER imports JAX: a chip belongs to one process at a time,
so every phase runs in a child that has the chip to itself and has exited before
the next starts, and the device facts in the last line are a child's. It exits
non-zero if any phase failed, raised, timed out, or ran on anything but a TPU.
The last line of stdout on success is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# Neither imports JAX (tests/test_chip_smoke.py holds the parent to that).
from tpu_resiliency.platform import compile_cache  # noqa: E402
from tpu_resiliency.utils.events import read_events  # noqa: E402

#: seconds the whole script may take (the driver allows 1200, compilation included)
DEADLINE_S = 1100
PHASE_TIMEOUT_S = {"telemetry": 300, "train": 600, "inprocess": 300, "multichip": 900}

#: what a run is sized to. ``full`` is the north-star telemetry configuration
#: (BASELINE.json) and the 160M-parameter model of ``_model_config``;
#: ``tiny`` keeps every control-flow step and shrinks only the arrays.
SIZES = {
    "full": dict(ranks=4096, signals=64, window=32, batch=8, seq=1024),
    "tiny": dict(ranks=512, signals=16, window=16, batch=2, seq=32),
}
#: phase ``train``: one async save lands in round 0 before the SIGKILL; the
#: straggler detector locks its report interval after 17 steps, so each round
#: runs past 20 to report at least twice.
TRAIN = dict(save_every=12, kill_after=22, total=36)
#: phase ``inprocess``: save at 4, raise after step index 5, re-enter, run to 10.
INPROCESS = dict(save_every=4, fault_after=6, total=10)
MULTICHIP_STEPS = 4
#: stated tolerance of the four-chip comparison: |loss_4chips - loss_1chip| per
#: step. bf16 activations summed in another order across tp=2 move a loss of
#: ~10.4 in the third decimal; the state itself is f32.
LOSS_TOLERANCE = 2e-2


# --------------------------------------------------------------------------
# shared by parent and children (no JAX at import)
# --------------------------------------------------------------------------

def write_json(path: str, doc: dict) -> None:
    with open(path, "w") as f:
        json.dump(doc, f)


def of_kind(events: list[dict], kind: str) -> list[dict]:
    return [e for e in events if e.get("kind") == kind]


class Check:
    """Collects named assertions: a phase is ok only if every one held."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def __call__(self, cond: bool, what: str) -> bool:
        if not cond:
            self.failed.append(what)
        return bool(cond)


# --------------------------------------------------------------------------
# children: everything below here may import JAX
# --------------------------------------------------------------------------

class WrongPlatform(SystemExit):
    """The chip check was asked for and JAX found something else."""


def device_facts() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def hbm_in_use() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["bytes_in_use"]) if stats else None


def cache_facts() -> dict:
    """This process's persistent-compile-cache traffic, as the package's own compile
    watcher counts it (``platform/compile_cache.py:watch``): every compile that
    consulted the cache, and every hit."""
    totals = compile_cache.compile_totals()
    return {"cache_requests": totals["requests"], "cache_hits": totals["hits"],
            "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR", "")}


def start_child_runtime(tiny: bool) -> dict:
    """What every child does first: take the device — at full size that is a
    TPU or the child stops here, before it places anything — then the
    compile-cache sweep and event (the directory is the parent's
    ``$JAX_COMPILATION_CACHE_DIR``), which also starts the compile watcher."""
    from tpu_resiliency.platform.device import apply_compile_cache_env

    device = device_facts()
    if not tiny and device["platform"] != "tpu":
        raise WrongPlatform(
            f"chip_smoke.py checks the system on a TPU; JAX found platform "
            f"{device['platform']!r} ({device['kind']}, {device['count']} device(s))"
        )
    apply_compile_cache_env()
    return device


def model_config(tiny: bool):
    from tpu_resiliency.models import transformer as tfm

    if tiny:
        return tfm.TransformerConfig.tiny()
    # 160M parameters, 1.9 GB of f32 params + AdamW moments: the model this
    # check has run with on the chip since PR 21 (CHANGES.md).
    return tfm.TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
        d_ff=2816, max_seq_len=1024,
    )


def make_tokens(cfg, sz: dict, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (sz["batch"], sz["seq"])).astype(np.int32)


def make_telemetry(seed: int, ranks: int, signals: int, window: int):
    """Timing windows ``[ranks, signals, window]`` with 5% noise in which 5% of the
    ranks, drawn from the seed, run 1.6x slow; the counts, and the truth mask."""
    import numpy as np

    rng = np.random.default_rng(seed)
    base = rng.uniform(0.8, 1.2, size=(1, signals, 1)).astype(np.float32)
    data = base * (1.0 + 0.05 * rng.standard_normal((ranks, signals, window)).astype(np.float32))
    slow_ranks = rng.choice(ranks, size=int(ranks * 0.05), replace=False)
    data[slow_ranks] *= 1.6
    counts = np.full((ranks, signals), window, dtype=np.int32)
    truth = np.zeros(ranks, dtype=bool)
    truth[slow_ranks] = True
    return data, counts, truth


def f1_score(pred_mask, truth) -> float:
    tp = int((pred_mask & truth).sum())
    fp = int((pred_mask & ~truth).sum())
    fn = int((~pred_mask & truth).sum())
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    return 2 * prec * rec / max(prec + rec, 1e-9)


def tree_nbytes(tree) -> int:
    import jax

    from tpu_resiliency.checkpoint.state_dict import tree_size_bytes

    return tree_size_bytes(jax.tree.leaves(tree))


def device_leaf_crcs(tree) -> list[int]:
    """CRC of every array leaf's bytes as the device holds them, in the order a
    container stores them (pytree flatten order)."""
    import jax
    import numpy as np

    from tpu_resiliency.checkpoint import format as ckpt_format

    return [
        ckpt_format.crc32c(memoryview(np.ascontiguousarray(np.asarray(x))).cast("B"))
        for x in jax.tree.leaves(tree)
    ]


def container_leaf_crcs(ckpt_dir: str, rank: int, iteration: int) -> tuple[list[int], int]:
    from tpu_resiliency.checkpoint import format as ckpt_format
    from tpu_resiliency.checkpoint.local_manager import CkptID

    path = os.path.join(ckpt_dir, "s0", f"r{rank}", CkptID(iteration, rank).filename())
    _, _, info = ckpt_format.read_trailer(path)
    return list(info.leaf_crcs), os.path.getsize(path)


# -- phase telemetry ---------------------------------------------------------

def phase_telemetry(args) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from tpu_resiliency.telemetry.device_profiler import DeviceTimeProfiler
    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    t_start = time.time()
    device = start_child_runtime(args.tiny)
    on_tpu = device["platform"] == "tpu"
    sz = SIZES["tiny" if args.tiny else "full"]
    r, s, w = sz["ranks"], sz["signals"], sz["window"]
    data, counts, truth = make_telemetry(args.seed, r, s, w)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("rank",))
    # On a TPU the kernel must be what auto-selection picks; anywhere else
    # auto-selection picks the XLA sort, so the rehearsal asks for the kernel
    # (interpret mode) to walk the same code.
    mt = MeshTelemetry(
        mesh, "rank", n_ranks=r, signal_names=tuple(f"sig{j}" for j in range(s)),
        window=w, use_pallas=None if on_tpu else True,
    )
    check = Check()
    check(mt.use_pallas is True, f"MeshTelemetry.use_pallas is {mt.use_pallas!r}, not True")

    rows = jnp.asarray(np.ascontiguousarray(np.transpose(data, (2, 0, 1))))  # [W, R, S]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(tstate, rows, i):
        # A train step's shape: the carry is donated, and this step's timing
        # row is appended to the device-resident rings inside the program.
        return mt.push(tstate, rows[i % w])

    state = mt.init_state()
    t0 = time.time()
    state = step(state, rows, jnp.int32(0))
    jax.block_until_ready(state)
    compile_push_s = time.time() - t0
    n_steps = max(64, 2 * w)
    for i in range(1, n_steps):
        state = step(state, rows, jnp.int32(i))
    jax.block_until_ready(state)

    compiled = mt._score_reset.lower(state).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if on_tpu:
        check(has_kernel, "compiled scorer has no tpu_custom_call (kernel missing or interpreted)")

    # One profiler window over the two programs a train loop pays for. The
    # extra pushes rewrite ring slots with the rows they already hold.
    prof = DeviceTimeProfiler()
    t0 = time.time()
    with prof:
        for i in range(n_steps, n_steps + w):
            state = mt.push(state, rows[i % w])
        state, scores = mt.score(state)
        jax.block_until_ready((state, scores))
    window_s = time.time() - t0
    stats = prof.get_stats()
    push_ms = [v["med"] * 1e3 for k, v in stats.items() if "_push_impl" in k]
    score_ms = [v["med"] * 1e3 for k, v in stats.items() if "_score_reset_impl" in k]
    check(bool(push_ms) and bool(score_ms), f"profiler window missed the ring programs: {sorted(stats)}")
    if on_tpu:
        check(prof.source == "device", f"profiler times came from {prof.source!r}, not a device plane")

    # The first round's historical minimum IS this round's median matrix.
    medians = np.asarray(scores.historical_min)
    reference = np.median(data, axis=-1).astype(np.float32)
    check(np.array_equal(medians, reference), "kernel medians are not bit-equal to numpy's")
    mask = np.asarray(scores.straggler)
    f1 = f1_score(mask, truth)
    check(f1 >= 0.99, f"straggler F1 {f1:.4f} < 0.99")
    check(bool(np.isfinite(np.asarray(scores.perf)).all()), "non-finite perf scores")

    return {
        "ok": not check.failed, "failed": check.failed, "device": device,
        "ranks": r, "signals": s, "window": w, "steps": n_steps,
        "use_pallas": mt.use_pallas, "kernel_in_program": has_kernel,
        "profiler_source": prof.source,
        "push_ms": push_ms[0] if push_ms else None,
        "score_ms": score_ms[0] if score_ms else None,
        "medians_bit_equal": bool(np.array_equal(medians, reference)),
        "f1": round(f1, 4), "flagged": int(mask.sum()), "truth": int(truth.sum()),
        "compile_push_s": round(compile_push_s, 2), "window_s": round(window_s, 2),
        "seconds": round(time.time() - t_start, 1), **cache_facts(),
    }


# -- the train function of phases train and inprocess -------------------------

def run_incarnation(args, plan: dict, ckpt_dir: str, label: dict, fault=None) -> dict:
    """One incarnation of the training job, as ``examples/resilient_training.py``
    runs its toy: restore the newest local checkpoint or initialise, then
    ``run_training`` with the resiliency callbacks to ``plan["total"]`` steps.
    ``fault(step_index, manager)`` is called at the end of each step, inside the
    step function (SIGKILL or raise; the loop swallows what a callback raises).
    Facts go to the structured event stream, kind ``smoke_*``, tagged ``label``."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
    from tpu_resiliency.integrations import (
        FaultToleranceCallback,
        HierarchicalCheckpointCallback,
        LoopContext,
        StragglerDetectionCallback,
        run_training,
    )
    from tpu_resiliency.models import transformer as tfm
    from tpu_resiliency.platform import ipc
    from tpu_resiliency.telemetry.ring_buffer import SignalRings
    from tpu_resiliency.utils.events import record as record_event

    rank = int(os.environ.get("RANK", "0"))
    world = int(os.environ.get("WORLD_SIZE", "1"))
    sz = SIZES["tiny" if args.tiny else "full"]
    cfg = model_config(args.tiny)
    hbm_at_entry = hbm_in_use()

    train_step, init_opt = tfm.make_train_step(cfg)
    # The state fills the chip (15.3 of 16 GB while a step runs): it is donated,
    # a second copy does not fit.
    step = jax.jit(train_step, donate_argnums=(0, 1))
    tokens = jnp.asarray(make_tokens(cfg, sz, args.seed))

    mgr = LocalCheckpointManager(ckpt_dir, rank=rank)
    ckpt_cb = HierarchicalCheckpointCallback(
        local_manager=mgr, local_every=plan["save_every"],
        to_state_dict=lambda st: {"params": st[0], "opt": st[1]},
        from_state_dict=lambda st, loaded: (loaded["params"], loaded["opt"]),
    )
    ctx = LoopContext(rank=rank, world_size=world)
    # Restore BEFORE anything else is placed: one copy of the state at a time.
    t0 = time.time()
    restored = ckpt_cb.restore_latest(ctx)
    if restored:
        jax.block_until_ready(ctx.state)
        restore_s = time.time() - t0
        want, file_bytes = container_leaf_crcs(ckpt_dir, rank, ctx.start_step)
        got = device_leaf_crcs({"params": ctx.state[0], "opt": ctx.state[1]})
        record_event(
            "smoke", "smoke_restored", step=ctx.start_step, leaves=len(got),
            crc_equal=got == want, state_bytes=tree_nbytes(ctx.state),
            file_bytes=file_bytes, restore_s=restore_s, **label,
        )
        if got != want:
            raise RuntimeError(
                f"restored leaves differ from the container's CRCs at "
                f"{[i for i, (a, b) in enumerate(zip(got, want)) if a != b]}"
            )
    else:
        params = tfm.init_params(jax.random.PRNGKey(args.seed), cfg)
        ctx.state = (params, jax.jit(init_opt)(params))
        del params
    record_event(
        "smoke", "smoke_incarnation", restored=bool(restored),
        start_step=ctx.start_step, state_bytes=tree_nbytes(ctx.state),
        n_params=sum(x.size for x in jax.tree.leaves(ctx.state[0])),
        hbm_at_entry=hbm_at_entry, rings_native=SignalRings(1, 1).native,
        device=device_facts(), **label,
    )

    def step_fn(state, i):
        t = time.time()
        params, opt_state, loss = step(*state, tokens)
        loss = float(loss)  # the host needs it: waits for the step
        record_event("smoke", "smoke_step", step=i, loss=loss,
                     ms=(time.time() - t) * 1e3, **label)
        if fault is not None:
            fault(i, mgr)
        return params, opt_state

    callbacks = [
        # The device options the straggler callback already has: report rounds
        # through the compiled mesh scorer (every step once the detector has
        # locked its interval, 17 steps in), program times from profiler
        # windows. 64 columns: the CPU rehearsal's host trace names every
        # PjitFunction it traced, and overflowing the scorer's capacity would
        # drop the mesh path for good.
        StragglerDetectionCallback(
            report_time_interval=0.0, use_device_mesh=True, use_pallas=True,
            profile_programs_every=3, mesh_signal_capacity=64,
        ),
        ckpt_cb,
    ]
    if os.environ.get(ipc.MONITOR_SOCKET_ENV):  # under the launcher's rank monitor
        callbacks.insert(0, FaultToleranceCallback(calc_timeouts=True))
    try:
        ctx = run_training(step_fn, ctx.state, plan["total"], callbacks=callbacks, ctx=ctx)
    finally:
        ckpt_cb.close()
    stats = jax.devices()[0].memory_stats() or {}
    record_event("smoke", "smoke_done", step=ctx.step,
                 hbm_peak=int(stats.get("peak_bytes_in_use", 0)) or None, **label)
    return {"step": ctx.step}


def phase_train_worker(args) -> dict:
    """The launcher's worker (``python chip_smoke.py --phase train ...``)."""
    from tpu_resiliency.launcher.errors import record
    from tpu_resiliency.utils.events import record as record_event

    @record
    def main():
        start_child_runtime(args.tiny)
        round_no = int(os.environ.get("TPU_FT_RESTART_COUNT", "0"))

        def kill_in_round_0(step_index, mgr):
            if round_no == 0 and step_index + 1 == TRAIN["kill_after"]:
                mgr.maybe_finalize(blocking=True)
                record_event("smoke", "smoke_kill", step=step_index,
                             latest=mgr.find_latest(), round=round_no, **cache_facts())
                os.kill(os.getpid(), signal.SIGKILL)

        out = run_incarnation(
            args, TRAIN, os.path.join(args.work, "ckpt"), {"round": round_no},
            fault=kill_in_round_0,
        )
        record_event("smoke", "smoke_cache", round=round_no, **cache_facts())
        return out

    return main()


# -- phase inprocess ---------------------------------------------------------

def phase_inprocess(args) -> dict:
    import jax

    from tpu_resiliency.inprocess import (
        AbortCompilationCache,
        AbortJaxDistributed,
        CallWrapper,
        Compose,
        JaxHealthCheck,
        Wrapper,
    )
    from tpu_resiliency.utils.events import record as record_event

    t_start = time.time()
    # The chip is taken BEFORE the wrapper forks its monitor daemon — the order a
    # user's script would have; the daemon closes every inherited descriptor.
    device = start_child_runtime(args.tiny)
    ckpt_dir = os.path.join(args.work, "ckpt_inprocess")
    ran: list[str] = []

    def traced(name, fn):
        def call(state):
            t = time.time()
            out = fn(state)
            ran.append(name)
            record_event("smoke", "smoke_abort_step", name=name,
                         s=time.time() - t, hbm=hbm_in_use(),
                         backend=jax.default_backend())
            return out
        return call

    class InjectedFault(RuntimeError):
        pass

    @Wrapper(
        abort=Compose(
            traced("AbortJaxDistributed", AbortJaxDistributed()),
            traced("AbortCompilationCache", AbortCompilationCache()),
        ),
        health_check=traced("JaxHealthCheck", JaxHealthCheck(timeout=60.0)),
        soft_timeout=240.0, hard_timeout=270.0, barrier_timeout=300.0,
        completion_timeout=300.0, heartbeat_timeout=120.0,
    )
    def train(call: CallWrapper):
        def raise_in_iteration_0(step_index, mgr):
            if call.iteration == 0 and step_index + 1 == INPROCESS["fault_after"]:
                mgr.maybe_finalize(blocking=True)
                raise InjectedFault(f"injected at step {step_index}")

        return run_incarnation(
            args, INPROCESS, ckpt_dir, {"iteration": call.iteration},
            fault=raise_in_iteration_0,
        )

    out = train()
    after = device_facts()
    check = Check()
    check(out == {"step": INPROCESS["total"]}, f"wrapped fn returned {out!r}")
    check(after == device, f"device changed across the restart: {device} -> {after}")
    check(ran == ["AbortJaxDistributed", "AbortCompilationCache", "JaxHealthCheck"],
          f"restart chain ran {ran}")
    return {"ok": not check.failed, "failed": check.failed, "device": after,
            "chain": ran, "seconds": round(time.time() - t_start, 1), **cache_facts()}


# -- phase multichip (--chips 4) ----------------------------------------------

def phase_multichip_worker(args) -> dict:
    """One worker, four chips: the flagship step on a dp x tp mesh, the
    telemetry scorer sharded four ways, an async save and a restore of the
    sharded state — each against one device of this same process."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
    from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
    from tpu_resiliency.launcher.errors import record
    from tpu_resiliency.models import transformer as tfm
    from tpu_resiliency.parallel import mesh as pmesh
    from tpu_resiliency.telemetry.sharded import MeshTelemetry
    from tpu_resiliency.utils.events import record as record_event

    @record
    def main():
        t_start = time.time()
        device = start_child_runtime(args.tiny)
        on_tpu = device["platform"] == "tpu"
        check = Check()
        if not check(device["count"] >= 4, f"needs 4 devices, JAX found {device['count']}"):
            return {"ok": False, "failed": check.failed, "device": device}
        devices = jax.devices()[:4]
        sz = SIZES["tiny" if args.tiny else "full"]
        cfg = model_config(args.tiny)
        tokens_np = make_tokens(cfg, sz, args.seed)
        train_step, init_opt = tfm.make_train_step(cfg)

        def fresh_state():
            params = tfm.init_params(jax.random.PRNGKey(args.seed), cfg)
            return params, jax.jit(init_opt)(params)

        def run_steps(step, params, opt_state, tokens):
            losses, ms = [], []
            for _ in range(MULTICHIP_STEPS):
                t = time.time()
                params, opt_state, loss = step(params, opt_state, tokens)
                losses.append(float(loss))
                ms.append((time.time() - t) * 1e3)
            return params, opt_state, losses, ms

        # -- one device first: it fills a chip, so it is gone before the mesh --
        one = jax.jit(train_step, donate_argnums=(0, 1))
        with jax.default_device(devices[0]):
            params, opt_state = fresh_state()
            params, opt_state, losses_1, ms_1 = run_steps(
                one, params, opt_state, jnp.asarray(tokens_np))
        del params, opt_state, one
        jax.clear_caches()

        # -- the same seeds on the dp x tp mesh ----------------------------------
        split = pmesh.default_split(4)
        mesh = pmesh.build_mesh(devices=devices, **split)
        pshard = pmesh.tree_shardings(mesh, pmesh.param_specs(cfg))
        params = jax.jit(
            lambda: tfm.init_params(jax.random.PRNGKey(args.seed), cfg),
            out_shardings=pshard,
        )()
        oshard = pmesh.opt_state_shardings(init_opt, params, pshard)
        opt_state = jax.jit(init_opt, out_shardings=oshard)(params)
        tokens = jax.device_put(tokens_np, NamedSharding(mesh, pmesh.batch_spec()))
        # Outputs pinned to the inputs' layout: the donated state goes round in
        # place, and the second step does not compile again.
        sharded = jax.jit(train_step, donate_argnums=(0, 1),
                          out_shardings=(pshard, oshard, None))
        step_text = sharded.lower(params, opt_state, tokens).compile().as_text()
        params, opt_state, losses_4, ms_4 = run_steps(sharded, params, opt_state, tokens)
        diffs = [abs(a - b) for a, b in zip(losses_1, losses_4)]
        check(all(np.isfinite(losses_1 + losses_4)), "non-finite loss")
        check(max(diffs) <= LOSS_TOLERANCE,
              f"loss per step differs by {max(diffs):.4g} > {LOSS_TOLERANCE}: {losses_1} vs {losses_4}")
        check(losses_4[-1] < losses_4[0], f"loss did not fall on the mesh: {losses_4}")
        check("all-reduce" in step_text, "sharded train step has no all-reduce")

        def holders(x):
            return sorted(s.device.id for s in x.addressable_shards)

        ids = sorted(d.id for d in devices)
        wq = params["layers"]["wq"]
        check(holders(wq) == ids, f"wq shards live on {holders(wq)}, not {ids}")
        check(wq.addressable_shards[0].data.shape[-1] * split["tp"] == wq.shape[-1],
              "wq is not split over tp")
        mu_wq = opt_state[0].mu["layers"]["wq"]
        check(holders(mu_wq) == ids and mu_wq.sharding == wq.sharding,
              f"AdamW's moments do not follow the params: {mu_wq.sharding}")
        check(holders(tokens) == ids, f"batch shards live on {holders(tokens)}")
        check(tokens.addressable_shards[0].data.shape[0] * split["dp"] == tokens.shape[0],
              "batch is not split over dp")

        # -- async save + restore of the sharded state ----------------------------
        tree = {"params": params, "opt": opt_state}
        shardings = jax.tree.map(lambda a: a.sharding, tree)
        crc_saved = device_leaf_crcs(tree)
        ckpt_dir = os.path.join(args.work, "ckpt_multichip")
        mgr = LocalCheckpointManager(ckpt_dir, rank=0)
        t = time.time()
        mgr.save(MULTICHIP_STEPS, PyTreeStateDict(tree), is_async=True)
        save_fg_s = time.time() - t
        mgr.maybe_finalize(blocking=True)
        save_s = time.time() - t
        state_bytes = tree_nbytes(tree)
        del tree, params, opt_state
        t = time.time()
        restored, _ = mgr.load_tree(mgr.find_latest(), shardings=shardings)
        jax.block_until_ready(restored)
        restore_s = time.time() - t
        crc_file, file_bytes = container_leaf_crcs(ckpt_dir, 0, MULTICHIP_STEPS)
        crc_restored = device_leaf_crcs(restored)
        check(crc_saved == crc_file, "container CRCs differ from the saved device state")
        check(crc_restored == crc_file, "restored leaves are not byte-equal to the container")
        rwq = restored["params"]["layers"]["wq"]
        check(holders(rwq) == ids and rwq.sharding == wq.sharding,
              f"restored wq lost its layout: {rwq.sharding}")
        mgr.close()
        del restored, rwq, wq, mu_wq

        # -- telemetry: four-way sharded scorer against the unsharded one ---------
        r, s, w = sz["ranks"], sz["signals"], sz["window"]
        data, counts, truth = make_telemetry(args.seed, r, s, w)
        rows = np.ascontiguousarray(np.transpose(data, (2, 0, 1)))
        names = tuple(f"sig{j}" for j in range(s))
        use_pallas = None if on_tpu else True

        def score_on(tmesh):
            mt = MeshTelemetry(tmesh, "rank", n_ranks=r, signal_names=names,
                               window=w, use_pallas=use_pallas)
            state = mt.init_state()
            for i in range(w):
                state = mt.push(state, jnp.asarray(rows[i]))
            text = mt._score_reset.lower(state).compile().as_text()
            shard_ids = holders(state.data)
            state, scores = mt.score(state)
            return mt, jax.device_get(mt._replicate(scores)), text, shard_ids

        mt4, sc4, text4, data_ids = score_on(Mesh(np.asarray(devices), ("rank",)))
        mt1, sc1, _, _ = score_on(Mesh(np.asarray(devices[:1]), ("rank",)))
        check(mt4.use_pallas is True and mt1.use_pallas is True,
              f"use_pallas {mt4.use_pallas!r}/{mt1.use_pallas!r}")
        if on_tpu:
            check("tpu_custom_call" in text4, "sharded scorer has no tpu_custom_call")
        check("all-gather" in text4 and "all-reduce" in text4,
              "sharded scorer lacks the all-gather / all-reduce of its cross-rank reductions")
        check(data_ids == ids, f"telemetry rings live on {data_ids}, not {ids}")
        score_diff = max(
            float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))
            for a, b in ((sc4.perf, sc1.perf), (sc4.z, sc1.z),
                         (sc4.section_scores, sc1.section_scores))
        )
        check(score_diff <= 1e-6, f"sharded scores differ from unsharded by {score_diff:.3g}")
        same_set = bool(np.array_equal(np.asarray(sc4.straggler), np.asarray(sc1.straggler)))
        check(same_set, "sharded and unsharded scorers flag different ranks")
        f1 = f1_score(np.asarray(sc4.straggler), truth)
        check(f1 >= 0.99, f"straggler F1 {f1:.4f} < 0.99")

        result = {
            "ok": not check.failed, "failed": check.failed, "device": device,
            "mesh": {k: v for k, v in split.items() if v > 1},
            "losses_1chip": losses_1, "losses_4chips": losses_4,
            "max_loss_diff": max(diffs), "loss_tolerance": LOSS_TOLERANCE,
            "step_ms_1chip": round(float(np.median(ms_1[1:])), 1),
            "step_ms_4chips": round(float(np.median(ms_4[1:])), 1),
            "shard_holders": ids, "state_bytes": state_bytes, "file_bytes": file_bytes,
            "save_foreground_s": round(save_fg_s, 3), "save_s": round(save_s, 2),
            "restore_s": round(restore_s, 2), "restored_byte_equal": crc_restored == crc_file,
            "telemetry_ranks_per_chip": r // 4, "use_pallas": mt4.use_pallas,
            "score_max_diff": score_diff, "same_straggler_set": same_set, "f1": round(f1, 4),
            "seconds": round(time.time() - t_start, 1), **cache_facts(),
        }
        return result

    return main()


CHILD_PHASES = {
    "telemetry": phase_telemetry,
    "train": phase_train_worker,
    "inprocess": phase_inprocess,
    "multichip": phase_multichip_worker,
}


def child_main(args) -> int:
    try:
        result = CHILD_PHASES[args.phase](args)
    except WrongPlatform as e:
        if args.out:
            write_json(args.out, {"ok": False, "wrong_platform": str(e), "failed": [str(e)]})
        raise
    except BaseException:
        if args.out:
            write_json(args.out, {"ok": False, "failed": [traceback.format_exc()[-4000:]]})
        raise
    if args.out:
        write_json(args.out, result)
    return 0 if result.get("ok", True) else 1


# --------------------------------------------------------------------------
# parent: never imports JAX
# --------------------------------------------------------------------------

def say(phase: str, facts: dict) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def build_native(tiny: bool) -> str:
    """``native`` or ``python``: which rings the children will run on. The
    extensions are built from the committed sources into this checkout; nothing
    here depends on a build product git would not commit. The tiny rehearsal
    does not build (it would change the tree under the tests running beside it)."""
    built = glob.glob(os.path.join(REPO, "tpu_resiliency", "_ringstats*.so"))
    if not built and not tiny:
        try:
            subprocess.run(
                [sys.executable, "setup.py", "-q", "build_ext", "--inplace"],
                cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, timeout=300,
            )
        except (OSError, subprocess.SubprocessError):
            pass
        built = glob.glob(os.path.join(REPO, "tpu_resiliency", "_ringstats*.so"))
    return "native" if built else "python"


#: every process this run starts inherits this variable with the run's token:
#: how the census tells them — a reparented monitor daemon included — from
#: anything else on the machine
RUN_TOKEN_ENV = "CHIP_SMOKE_RUN"


class Census(threading.Thread):
    """Who holds the chip while a phase runs: samples every process of this run
    (by :data:`RUN_TOKEN_ENV` in its environment) and notes its open device
    nodes (``/dev/vfio/*``, ``/dev/accel*`` — what made a second client fail
    with "Device or resource busy") and whether libtpu is mapped."""

    def __init__(self, token: str) -> None:
        super().__init__(daemon=True)
        self.mark = f"{RUN_TOKEN_ENV}={token}".encode()
        self.seen: dict[int, dict] = {}
        self._halt = threading.Event()

    def sample(self) -> None:
        for entry in os.listdir("/proc"):
            if not entry.isdigit() or int(entry) == os.getpid():
                continue
            base = f"/proc/{entry}"
            try:
                with open(f"{base}/environ", "rb") as f:
                    if self.mark not in f.read().split(b"\0"):
                        continue
                with open(f"{base}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
                rec = self.seen.setdefault(
                    int(entry), {"cmd": cmd, "dev_fds": set(), "libtpu": False, "held": 0})
                held = False
                for fd in os.listdir(f"{base}/fd"):
                    try:
                        target = os.readlink(f"{base}/fd/{fd}")
                    except OSError:
                        continue
                    if target.startswith(("/dev/vfio", "/dev/accel")):
                        rec["dev_fds"].add(target)
                        held = True
                rec["held"] += held  # samples (0.25 s apart) it held the chip in
                with open(f"{base}/maps") as f:
                    rec["libtpu"] = rec["libtpu"] or "libtpu" in f.read()
            except OSError:
                continue  # the process went away mid-sample

    def run(self) -> None:
        while not self._halt.wait(0.25):
            self.sample()

    def stop(self) -> dict[int, dict]:
        self._halt.set()
        self.join(5)
        return self.seen

    def leftovers(self) -> list[int]:
        self.sample()
        return [pid for pid in self.seen if os.path.exists(f"/proc/{pid}")]


def role_of(cmd: str, pid: int, worker_pids: set[int], launcher_pid: int | None) -> str:
    if pid in worker_pids:
        return "worker"
    if pid == launcher_pid:
        return "launcher"
    if "launcher.park" in cmd:
        return "spare"
    if "launcher.launch" in cmd:
        return "monitor"  # a fork of the launcher keeps its command line
    return "other"


def run_process(argv: list[str], env: dict, log_path: str, timeout: float):
    """Run to the end or kill the whole process group; stdio goes to a file (a
    launcher's children inherit it — pipes would deadlock)."""
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(argv, env=env, stdout=log, stderr=log,
                                start_new_session=True, cwd=REPO)
        try:
            return proc.pid, proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
            return proc.pid, "timeout"


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError:
        return ""


def launcher_logs(work: str, phase: str) -> str:
    """The end of the launcher's log and of every worker's stderr it captured."""
    workers = sorted(glob.glob(os.path.join(work, f"logs_{phase}", "*", "*", "stderr.log")))
    return "\n".join(
        f"--- {os.path.relpath(p, work)}\n{tail(p, 2000)}"
        for p in [os.path.join(work, f"{phase}.log"), *workers]
    )


def child_argv(phase: str, args, work: str, out: str | None) -> list[str]:
    argv = [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--work", work, "--seed", str(args.seed)]
    if out:
        argv += ["--out", out]
    if args.tiny:
        argv.append("--tiny")
    return argv


def launcher_argv(phase: str, args, work: str, out: str | None, events: str,
                  extra: list[str]) -> list[str]:
    return [
        sys.executable, "-m", "tpu_resiliency.launcher.launch",
        "--standalone", "--nproc-per-node", "1", "--max-restarts", "2",
        # short: the agent's unix sockets live here, and a path has 108 bytes
        "--events-file", events, "--run-dir", os.path.join(work, "r"),
        "--log-dir", os.path.join(work, f"logs_{phase}"), *extra,
        *child_argv(phase, args, work, out)[1:],
    ]


def read_result(out: str, log: str) -> dict:
    try:
        with open(out) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {"ok": False, "failed": [f"no result file; log tail: {tail(log)}"]}


def direct_phase(phase: str, args, work: str, env: dict, timeout: float) -> dict:
    """A phase that is one child process holding the chip."""
    out = os.path.join(work, f"{phase}.json")
    log = os.path.join(work, f"{phase}.log")
    census = Census(env[RUN_TOKEN_ENV])
    census.start()
    t0 = time.time()
    pid, rc = run_process(child_argv(phase, args, work, out), env, log, timeout)
    seen = census.stop()
    result = read_result(out, log)
    check = Check()
    check(rc == 0, f"child exit {rc}")
    if rc != 0 and not result.get("failed"):
        result["failed"] = [f"log tail: {tail(log)}"]
    # A fork on its way out may show the inherited nodes in one sample.
    others = {p: r for p, r in seen.items() if p != pid and r["held"] > 1}
    check(not others, f"processes other than the phase's child held the chip: {others}")
    result["failed"] = list(result.get("failed", [])) + check.failed
    result["ok"] = bool(result.get("ok")) and not check.failed
    result["wall_s"] = round(time.time() - t0, 1)
    result["other_processes"] = [
        {"pid": p, "dev_fds": sorted(r["dev_fds"]), "held_samples": r["held"],
         "libtpu_mapped": r["libtpu"]}
        for p, r in seen.items() if p != pid
    ]
    return result


def train_phase(args, work: str, env: dict, timeout: float, on_tpu_required: bool) -> dict:
    """Phase ``train``: the launcher, two rounds, judged from the events file."""
    events_path = os.path.join(work, "train_events.jsonl")
    log = os.path.join(work, "train.log")
    census = Census(env[RUN_TOKEN_ENV])
    census.start()
    t0 = time.time()
    launcher_pid, rc = run_process(
        launcher_argv("train", args, work, None, events_path,
                      ["--warm-spares", "1", "--monitor-interval", "0.1"]),
        env, log, timeout,
    )
    seen = census.stop()
    ev = read_events(events_path)
    kinds = functools.partial(of_kind, ev)
    check = Check()
    check(rc == 0, f"launcher exit {rc}; logs: {launcher_logs(work, 'train')}")

    rounds = [e["round"] for e in kinds("rendezvous_round")]
    check(rounds == [0, 1], f"rendezvous rounds {rounds}, want [0, 1]")
    inc = sorted(kinds("smoke_incarnation"), key=lambda e: e["round"])
    check([e["round"] for e in inc] == [0, 1], f"worker incarnations {[e['round'] for e in inc]}")
    check(len({e["pid"] for e in inc}) == len(inc), "both rounds ran in one process")
    platforms = [e["device"]["platform"] for e in inc]
    if on_tpu_required:
        check(platforms == ["tpu", "tpu"], f"worker platforms by round: {platforms}")
    check([e["restored"] for e in inc] == [False, True], "round 0 must initialise, round 1 restore")

    saved = [e for e in kinds("ckpt_saved")]
    kill = kinds("smoke_kill")
    check(len(kill) == 1 and kill[0]["latest"] == TRAIN["save_every"],
          f"round 0 did not die with a finalized save at {TRAIN['save_every']}: {kill}")
    round0_saves = [e for e in saved if kill and e["ts"] <= kill[0]["ts"]]
    check(bool(round0_saves), "no ckpt_saved (finalized save) before the SIGKILL")
    restored = kinds("smoke_restored")
    check(len(restored) == 1 and restored[0]["crc_equal"] is True
          and restored[0]["step"] == TRAIN["save_every"],
          f"restore proof missing or failed: {restored}")
    loads = [e for e in kinds("timing") if e.get("name") == "ckpt.local_load"]
    check(any(e.get("ok") for e in loads), "no successful ckpt.local_load timing event")

    steps = {r: [e for e in kinds("smoke_step") if e["round"] == r] for r in (0, 1)}
    idx = {r: [e["step"] for e in steps[r]] for r in (0, 1)}
    check(idx[0] == list(range(TRAIN["kill_after"])), f"round 0 steps {idx[0]}")
    check(idx[1] == list(range(TRAIN["save_every"], TRAIN["total"])), f"round 1 steps {idx[1]}")
    losses = [e["loss"] for r in (0, 1) for e in steps[r]]
    finite = all(isinstance(x, float) and x == x and abs(x) != float("inf") for x in losses)
    check(finite and bool(losses), "non-finite or missing losses")
    if finite and steps[0] and steps[1]:
        check(steps[1][-1]["loss"] < steps[0][0]["loss"],
              f"loss did not fall: {steps[0][0]['loss']} -> {steps[1][-1]['loss']}")
        # The restored state continues the run it was saved from: the resumed
        # step repeats round 0's step at the same index, same batch.
        first, again = steps[1][0]["loss"], steps[0][TRAIN["save_every"]]["loss"]
        check(abs(first - again) <= 1e-3 * max(1.0, abs(again)),
              f"resumed step {TRAIN['save_every']} loss {first} != round 0's {again}")

    reports = kinds("straggler_report")
    check(len(reports) >= 2, f"{len(reports)} straggler reports, want >= 2")
    sources = sorted({e.get("report_source") for e in reports})
    check(sources == ["mesh"], f"straggler reports came through {sources}, not the mesh path")
    psources = sorted({str(e.get("profile_source")) for e in reports})
    if on_tpu_required:
        check(psources == ["device"], f"profiler windows read {psources}, not a device plane")
    check(all(e.get("profile_dropped") == 0 and e.get("profile_skipped") == 0 for e in reports),
          "a profiler window was skipped or dropped")
    prog = sorted({s for e in reports for s in e.get("signals", []) if s.startswith("prog/")})
    check(any("train_step" in s for s in prog), f"no prog/...train_step signal: {prog}")

    cc = {e["pid"]: e["outcome"] for e in kinds("compile_cache")}
    by_round = [cc.get(e["pid"]) for e in inc]
    cache_ev = {e["round"]: e for e in kinds("smoke_cache") + kinds("smoke_kill")}
    check(len(by_round) == 2 and by_round[1] == "hit", f"compile_cache outcome by round: {by_round}")
    check(cache_ev.get(1, {}).get("cache_hits", 0) >= 1,
          f"round 1 loaded nothing from the compile cache: {cache_ev.get(1)}")
    # ... and the step itself, by the watcher's own event of it: which program, not how many
    step_cache = [[c["cache"] for c in kinds("compile")
                   if c["pid"] == e["pid"] and c["fun_name"] == "jit(train_step)"] for e in inc]
    check(len(step_cache) == 2 and bool(step_cache[1]) and set(step_cache[1]) == {"hit"},
          f"round 1 did not load the step from the compile cache: {step_cache}")

    worker_pids = {e["pid"] for e in inc}
    table = []
    for pid, rec in sorted(seen.items()):
        role = role_of(rec["cmd"], pid, worker_pids, launcher_pid)
        table.append({"pid": pid, "role": role, "dev_fds": sorted(rec["dev_fds"]),
                      "libtpu_mapped": rec["libtpu"]})
        if role != "worker":
            check(not rec["dev_fds"] and not rec["libtpu"],
                  f"{role} pid {pid} touched the chip: {sorted(rec['dev_fds'])} libtpu={rec['libtpu']}")
    promoted = [e.get("outcome") for e in kinds("worker_promoted")]

    ms = {r: [e["ms"] for e in steps[r]] for r in (0, 1)}
    stream = [e for e in kinds("timing") if e.get("name") == "ckpt.save.stream" and e.get("ok")]
    fg = kinds("ckpt_foreground_blocked")
    return {
        "ok": not check.failed, "failed": check.failed,
        "device": inc[-1]["device"] if inc else None,
        "model": {"n_params": inc[0]["n_params"], "state_bytes": inc[0]["state_bytes"],
                  "batch_x_seq": [SIZES["tiny" if args.tiny else "full"][k] for k in ("batch", "seq")]} if inc else None,
        "rounds": rounds, "worker_platforms": platforms, "promotions": promoted,
        "steps": {str(r): len(idx[r]) for r in (0, 1)},
        "first_step_ms": {str(r): round(ms[r][0], 1) for r in (0, 1) if ms[r]},
        "step_ms_median": {str(r): round(statistics.median(ms[r][1:]), 1) for r in (0, 1) if len(ms[r]) > 1},
        "loss_first_last": [losses[0], losses[-1]] if losses else None,
        "saves": [{"iteration": e["iteration"], "bytes": e.get("bytes")} for e in saved],
        "save_stream_s": [round(e["duration_s"], 2) for e in stream],
        "save_foreground_s": [
            {"engine": e.get("engine"), "s": round(e["duration_s"], 3)} for e in fg],
        "restore": {k: restored[0][k] for k in
                    ("step", "leaves", "crc_equal", "state_bytes", "file_bytes", "restore_s")}
        if restored else None,
        "report_source": sources, "profile_source": psources, "reports": len(reports),
        "prog_signals": prog, "rings_native": inc[-1]["rings_native"] if inc else None,
        "compile_cache_by_round": by_round, "step_cache_by_round": step_cache,
        "cache_counts_by_round": {str(r): {k: e[k] for k in ("cache_requests", "cache_hits")}
                                  for r, e in sorted(cache_ev.items())},
        "cache_dir": cache_ev.get(1, {}).get("cache_dir"),
        "hbm_peak": next((e.get("hbm_peak") for e in kinds("smoke_done")), None),
        "processes": table, "wall_s": round(time.time() - t0, 1),
    }


def inprocess_phase(args, work: str, env: dict, timeout: float) -> dict:
    events_path = os.path.join(work, "inprocess_events.jsonl")
    result = direct_phase(
        "inprocess", args, work,
        {**env, "TPU_RESILIENCY_EVENTS_FILE": events_path, "TPU_RESILIENCY_STORE_PORT": "0"},
        timeout,
    )
    ev = read_events(events_path)
    kinds = functools.partial(of_kind, ev)
    check = Check()
    inc = sorted(kinds("smoke_incarnation"), key=lambda e: e["iteration"])
    check([e["iteration"] for e in inc] == [0, 1], f"incarnations {[e['iteration'] for e in inc]}")
    check(len({e["pid"] for e in inc}) == 1, "the restart left the process")
    check([e["restored"] for e in inc] == [False, True], "iteration 1 must restore")
    check(len(kinds("fn_exception")) == 1, "no fn_exception event")
    restored = kinds("smoke_restored")
    check(len(restored) == 1 and restored[0]["crc_equal"] is True, f"restore proof: {restored}")
    idx = {i: [e["step"] for e in kinds("smoke_step") if e["iteration"] == i] for i in (0, 1)}
    check(idx[0] == list(range(INPROCESS["fault_after"])), f"iteration 0 steps {idx[0]}")
    check(idx[1] == list(range(INPROCESS["save_every"], INPROCESS["total"])),
          f"iteration 1 steps {idx[1]}")
    losses = [e["loss"] for e in kinds("smoke_step")]
    check(bool(losses) and all(x == x for x in losses) and losses[-1] < losses[0],
          f"loss did not fall: {losses[:1]} -> {losses[-1:]}")
    # HBM at restart: the previous incarnation's state must be gone when the
    # restored copy lands (only the backend reports it; the CPU does not).
    entry = [e.get("hbm_at_entry") for e in inc]
    state_bytes = inc[0]["state_bytes"] if inc else 0
    if len(entry) == 2 and entry[1] is not None:
        check(entry[1] < state_bytes / 2,
              f"{entry[1]} B still on the device at re-entry (state is {state_bytes} B)")
    result["failed"] = list(result.get("failed", [])) + check.failed
    result["ok"] = bool(result.get("ok")) and not check.failed
    ms = {i: [e["ms"] for e in kinds("smoke_step") if e["iteration"] == i] for i in (0, 1)}
    result.update(
        steps={str(i): len(idx[i]) for i in (0, 1)},
        first_step_ms={str(i): round(ms[i][0], 1) for i in (0, 1) if ms[i]},
        step_ms_median={str(i): round(statistics.median(ms[i][1:]), 1) for i in (0, 1) if len(ms[i]) > 1},
        hbm_at_entry=entry, state_bytes=state_bytes,
        abort_steps=[{k: e.get(k) for k in ("name", "s", "hbm", "backend")}
                     for e in kinds("smoke_abort_step")],
        restart_s=[round(e["duration_s"], 3) for e in kinds("span_end")
                   if e.get("span") == "inprocess.restart"] or None,
        restore=({k: restored[0][k] for k in ("step", "leaves", "crc_equal", "restore_s")}
                 if restored else None),
        hbm_peak=next((e.get("hbm_peak") for e in kinds("smoke_done")), None),
    )
    return result


def multichip_phase(args, work: str, env: dict, timeout: float) -> dict:
    """``--chips 4``: the launcher starts one worker that drives all four chips."""
    out = os.path.join(work, "multichip.json")
    events_path = os.path.join(work, "multichip_events.jsonl")
    log = os.path.join(work, "multichip.log")
    t0 = time.time()
    _, rc = run_process(
        launcher_argv("multichip", args, work, out, events_path, ["--max-restarts", "0"]),
        env, log, timeout,
    )
    result = read_result(out, log)
    check = Check()
    check(rc == 0, f"launcher exit {rc}; logs: {launcher_logs(work, 'multichip')}")
    rounds = [e["round"] for e in of_kind(read_events(events_path), "rendezvous_round")]
    check(rounds == [0], f"rendezvous rounds {rounds}, want one worker in one round")
    result["failed"] = list(result.get("failed", [])) + check.failed
    result["ok"] = bool(result.get("ok")) and not check.failed
    result["wall_s"] = round(time.time() - t0, 1)
    return result


def parent_main(args) -> int:

    t_start = time.time()
    want_count = 4 if args.chips == 4 else 1
    env = dict(os.environ)
    # One persistent compile cache for every child: where the environment names
    # it, there; otherwise this checkout's one fixed directory.
    env.setdefault(compile_cache.CACHE_DIR_ENV, compile_cache.checkout_cache_dir(REPO))
    env["PYTHONPATH"] = os.pathsep.join(p for p in (REPO, env.get("PYTHONPATH", "")) if p)
    env.setdefault("TPU_RESILIENCY_LOG_LEVEL", "INFO")
    say("setup", {
        "mode": "tiny" if args.tiny else "full", "chips": args.chips, "seed": args.seed,
        "cache_dir": env[compile_cache.CACHE_DIR_ENV],
        "cache_dir_from": "environment" if compile_cache.CACHE_DIR_ENV in os.environ
        else "checkout", "sizes": SIZES["tiny" if args.tiny else "full"],
    })
    work = tempfile.mkdtemp(prefix="cs_")
    env[RUN_TOKEN_ENV] = os.path.basename(work)
    results: dict[str, dict] = {}
    census = Census(env[RUN_TOKEN_ENV])
    rings = None
    try:
        phases = ["multichip"] if args.chips == 4 else ["telemetry", "train", "inprocess"]
        for phase in phases:
            if phase in (args.skip or []):
                continue
            left = DEADLINE_S - (time.time() - t_start)
            timeout = min(PHASE_TIMEOUT_S[phase], left)
            if phase in ("train", "inprocess") and rings is None:
                # Not before a phase has found the chip: off one, nothing is built.
                rings = build_native(args.tiny)
                say("native", {"rings": rings})
            if timeout <= 10:
                results[phase] = {"ok": False, "failed": ["no time left for this phase"]}
            elif phase == "train":
                results[phase] = train_phase(args, work, env, timeout, not args.tiny)
            elif phase == "inprocess":
                results[phase] = inprocess_phase(args, work, env, timeout)
            elif phase == "multichip":
                results[phase] = multichip_phase(args, work, env, timeout)
            else:
                results[phase] = direct_phase(phase, args, work, env, timeout)
            say(phase, results[phase])
            if results[phase].get("wrong_platform"):
                break  # no chip: the other phases would only say so again
    finally:
        # Stop every process this script started (a wrapper's monitor daemon
        # leaves on its own once its rank is gone; give it a moment).
        time.sleep(1.0)
        stray = census.leftovers()
        for pid in stray:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        shutil.rmtree(work, ignore_errors=True)
    if not args.tiny:  # the rehearsal writes nothing into the checkout
        summary_dir = os.path.join(REPO, "chiprun_out")
        try:
            os.makedirs(summary_dir, exist_ok=True)
            write_json(os.path.join(summary_dir, f"chip_smoke_{args.chips}chip.json"), results)
        except OSError:
            pass

    failed = {p: r.get("failed") for p, r in results.items() if not r.get("ok")}
    devices = [r.get("device") for r in results.values() if r.get("device")]
    device = devices[-1] if devices else None
    seconds = round(time.time() - t_start, 1)
    wrong = [r["wrong_platform"] for r in results.values() if r.get("wrong_platform")]
    if wrong:
        print(f"chip_smoke: FAILED: {wrong[0]}. No result.", file=sys.stderr)
        return 1
    if failed or not results:
        print(f"chip_smoke: FAILED after {seconds}s: {json.dumps(failed)[:6000]}", file=sys.stderr)
        return 1
    if args.tiny or args.skip:
        print(f"chip_smoke: the rehearsal ({'tiny' if args.tiny else 'partial'}) passed "
              f"on {device} in {seconds}s; it is not the chip check and prints no result.",
              file=sys.stderr)
        return 0
    if any(d != device for d in devices) or device["platform"] != "tpu" \
            or device["count"] != want_count:
        print(
            f"chip_smoke: every phase passed, but on platform {device['platform']!r} "
            f"({device['kind']}, {device['count']} device(s)); this check needs "
            f"{want_count} TPU chip(s). No result.", file=sys.stderr)
        return 1
    say("total", {"seconds": seconds})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the path across four chips and its comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the control flow at a tiny size on any backend; never prints ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip", action="append", choices=sorted(CHILD_PHASES),
                    help="leave a phase out (the run then cannot print ok)")
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES), help="internal: run one phase in this process")
    ap.add_argument("--work", help="internal: the run's work directory")
    ap.add_argument("--out", help="internal: where the phase writes its result")
    args = ap.parse_args()
    if args.phase:
        return child_main(args)
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
