"""One yardstick: speed is what ``benchmark/run.py`` reads on the chip and the
driver writes into ``PERF_LEDGER.jsonl``. No measuring program or record from
before the chip stands beside it at the root, and nothing the repo ships cites one."""

import fnmatch
import os
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tracked(*paths):
    """The files git would commit under ``paths`` (everything, with none given); in
    a tree that is no git checkout, the files that are there."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--", *paths], cwd=REPO, capture_output=True, text=True,
            check=True,
        )
        if out.stdout.strip():
            return out.stdout.split("\n")[:-1]
    except (OSError, subprocess.CalledProcessError):
        pass
    found = []
    for top in paths or ["."]:
        if os.path.isfile(os.path.join(REPO, top)):
            found.append(top)
        for root, dirs, names in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".") and d not in ("__pycache__", "chiprun_out")]
            found += [os.path.relpath(os.path.join(root, n), REPO) for n in names]
    return found


def test_the_root_holds_no_side_benchmark_or_record():
    at_root = [p for p in tracked() if "/" not in p]
    assert "README.md" in at_root  # the listing is of this repo
    old = [
        p for p in at_root
        if any(fnmatch.fnmatch(p, pat)
               for pat in ("BENCH_*", "MULTICHIP_*", "*.log", "bench.py"))
    ]
    assert old == []


def test_nothing_shipped_cites_a_deleted_record_or_program():
    needles = ("BENCH_", "scripts/bench_")
    citing = []
    for path in tracked("tpu_resiliency", "docs", "scripts", "examples", "README.md"):
        with open(os.path.join(REPO, path), errors="replace") as f:
            text = f.read()
        citing += [f"{path}: {n}" for n in needles if n in text]
    assert citing == []
