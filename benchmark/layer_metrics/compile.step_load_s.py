"""Seconds of set-up the backend spent handing over the step: over the program's
``compile`` events (``tpu_resiliency/platform/compile_cache.py:watch``, one per
executable, from JAX's own timers on the host) recorded before the window opened whose
``fun_name`` is the family contract's ``train_step`` (``jit(train_step)``), the sum of
``backend_s``: the load from the persistent cache on a hit, the compile on a miss (what
the program's code bytes and the machine's cache cap set).

:func:`in_setup` reads the events once a run for this reader and for
``compile.step_trace_s``, and prints the log line ``compile_in_setup``: every program of
set-up by ``fun_name`` with its seconds and its ``cache`` outcomes, the ten largest
first, and the totals. The reference's programs (``jit(<lambda>)``, ``jit(_adamw_leaf)``,
``jit(_norm)``) are in that line by name and in neither metric: their seconds are
excluded from ``setup_s`` already. On a program that records no ``compile`` event (a
commit from before the watcher) there is nothing to read and both return ``None``."""

#: the program the two metrics are taken of
STEP = "jit(train_step)"
PARTS = ("trace_s", "lower_s", "backend_s")


def in_setup(run) -> dict | None:
    """``{"trace_s", "lower_s", "backend_s"}`` summed over the step's ``compile`` events
    of set-up, or ``None`` where the program wrote none."""
    if "compile_in_setup" in run.notes:
        return run.notes["compile_in_setup"]
    events = [e for e in run.events if e.get("kind") == "compile"
              and (run.t_open is None or e["ts"] < run.t_open)]
    programs: dict[str, dict] = {}
    for e in events:
        row = programs.setdefault(e["fun_name"], {"programs": 0, "seconds": 0.0, "cache": {}})
        row["programs"] += 1
        row["seconds"] += sum(e[k] for k in PARTS)
        row["cache"][e["cache"]] = row["cache"].get(e["cache"], 0) + 1
    step = [e for e in events if e["fun_name"] == STEP]
    found = {k: sum(e[k] for e in step) for k in PARTS} if step else None
    if events:
        largest = sorted(programs.items(), key=lambda kv: -kv[1]["seconds"])[:10]
        run.say("compile_in_setup", programs=len(events),
                seconds=sum(row["seconds"] for row in programs.values()),
                by_cache={c: sum(row["cache"].get(c, 0) for row in programs.values())
                          for c in ("hit", "miss", "uncached")},
                step=found and {**found, "cache": [e["cache"] for e in step]},
                largest=[{"fun_name": name, **row} for name, row in largest])
    run.notes["compile_in_setup"] = found
    return found


def read(run):
    found = in_setup(run)
    return found["backend_s"] if found else None
