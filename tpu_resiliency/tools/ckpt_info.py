"""Operator view of a local-checkpoint root: holdings, coverage, health.

The local tier's layout is self-describing (``checkpoint/local_manager.py``:
``root/s{session}/r{rank}/iter_NNNNNNN_{owner}_local.ckpt`` — the directory
names the *holder*, the filename the *owner*), so coverage — the property
``find_latest`` needs (some live holder for every owner's shard) — can be
audited offline from the filesystem alone, without the job's comm group. This
is the post-mortem twin of the in-job coverage check: "which iteration could a
restarted world actually resume from, and what is replication costing me?"

``--cold <dir>`` joins the durable cold tier (``checkpoint/coldtier.py``) to
the audit: archived owners count toward per-iteration coverage (the in-job
ladder's third rung, rendered per iteration as local / erasure-reconstructible
/ cold), sessions that exist only in the object store are auditable from an
empty workdir, and ``--verify`` re-checks every archived artifact against its
cold manifest's whole-file digest.

``--verify`` additionally stream-verifies every container's checksums
(per-leaf and per-chunk CRCs + trailer digest, ``checkpoint/format.py``), prints a
per-file verdict, and exits 1 on any mismatch — an operator preflight before
trusting a root for restart, and a CI gate after fault-injection runs.

``--world <ranks> --plan`` renders the elastic reshard plan the given target
world would execute (``checkpoint/reshard.py``): per target rank, each leaf's
source cells with owner ranks, byte ranges, and the local-slice vs peer-fetch
split implied by what's on disk — without loading a single tensor. Exits 1
when any needed range has no surviving source container ("coverage
impossible", naming the missing ranks).

Usage::

    python -m tpu_resiliency.tools.ckpt_info /ssd/ckpt-root
    python -m tpu_resiliency.tools.ckpt_info /ssd/ckpt-root --session 1
    python -m tpu_resiliency.tools.ckpt_info /ssd/ckpt-root --verify
    python -m tpu_resiliency.tools.ckpt_info /ssd/ckpt-root \
        --cold /backup/cold --verify
    python -m tpu_resiliency.tools.ckpt_info /ssd/ckpt-root --world 0,1,2 --plan
    python -m tpu_resiliency.tools.ckpt_info /ssd/ckpt-root --world 0,1,2,3 \
        --plan --axes dp=2,tp=2
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
import sys
from typing import Optional

from tpu_resiliency.checkpoint.local_manager import (
    _BLOCK_RE,
    _CORRUPT_RE,
    _FILE_RE,
)
from tpu_resiliency.tools import SIGPIPE_EXIT, pipe_safe

_SESSION_RE = re.compile(r"^s(\d+)$")
_RANK_RE = re.compile(r"^r(\d+)$")


@dataclasses.dataclass
class SessionInfo:
    session: int
    #: rank dirs present (the world this root has seen)
    ranks: set
    #: iteration -> owner -> set of holder ranks
    holdings: dict
    #: iteration -> total bytes across all copies
    bytes_by_iter: dict
    #: leftover .dirty temp files (crashed mid-save)
    dirty: list
    #: quarantined *.corrupt files (checksum-failed containers kept for
    #: forensics by the recovery ladder)
    quarantined: list = dataclasses.field(default_factory=list)
    #: container files eligible for --verify: [(path, holder, iter, owner)]
    files: list = dataclasses.field(default_factory=list)
    #: erasure block artifacts: iteration -> owner -> {index: set(holders)}
    #: plus the code's k per (iteration, owner) — k-of-n coverage input
    blocks: dict = dataclasses.field(default_factory=dict)
    block_k: dict = dataclasses.field(default_factory=dict)
    #: block artifact files: [(path, holder, iter, owner, index)]
    block_files: list = dataclasses.field(default_factory=list)
    #: cold-tier coverage (``--cold``): iteration -> set of owners whose
    #: containers the object store archives with a valid manifest
    cold: dict = dataclasses.field(default_factory=dict)

    @property
    def owners(self) -> set:
        out = set()
        for by_owner in self.holdings.values():
            out |= set(by_owner)
        for by_owner in self.blocks.values():
            out |= set(by_owner)
        for owners in self.cold.values():
            out |= set(owners)
        return out

    def reconstructible(self, it: int) -> set:
        """Owners whose shard k-of-n erasure blocks can reassemble at ``it``
        (≥ k distinct surviving block indices)."""
        out = set()
        for owner, by_index in self.blocks.get(it, {}).items():
            if len(by_index) >= self.block_k.get((it, owner), 1 << 30):
                out.add(owner)
        return out

    def covered_iterations(self, world: Optional[set] = None) -> list:
        """Iterations where every rank of ``world`` finds its shard held
        somewhere — a full container on some holder, enough erasure blocks
        to reconstruct one, or (with ``--cold``) an archived copy in the
        cold tier (the offline analogue of ``_covered_iterations`` with its
        third rung).

        Coverage is **group-relative**: a restarted group resumes from the
        newest iteration whose owner set covers *that group* — after an
        elastic shrink the surviving world legitimately resumes from data the
        full original world could not. Default world: everything the
        filesystem shows (rank dirs plus every owner ever named), i.e. the
        original full world."""
        world = (self.ranks | self.owners) if world is None else set(world)
        its = set(self.holdings) | set(self.blocks) | set(self.cold)
        return sorted(
            it
            for it in its
            if world
            <= (
                set(self.holdings.get(it, ()))
                | self.reconstructible(it)
                | set(self.cold.get(it, ()))
            )
        )


def scan(root: str, session: Optional[int] = None) -> list[SessionInfo]:
    """Offline-but-live-safe: a training job's retention pruning can unlink
    files between listing and stat'ing, so every per-entry touch tolerates
    disappearance (the audit then simply reflects the post-prune state)."""
    sessions = []
    try:
        snames = sorted(os.listdir(root))
    except OSError:
        return []  # root itself unlinked mid-audit: post-prune state is "empty"
    for sname in snames:
        sm = _SESSION_RE.match(sname)
        if not sm or (session is not None and int(sm.group(1)) != session):
            continue
        info = SessionInfo(int(sm.group(1)), set(), {}, {}, [])
        sdir = os.path.join(root, sname)
        try:
            rnames = sorted(os.listdir(sdir))
        except OSError:
            continue  # session dir unlinked between the two listings
        for rname in rnames:
            rm = _RANK_RE.match(rname)
            if not rm:
                continue
            holder = int(rm.group(1))
            info.ranks.add(holder)
            rdir = os.path.join(sdir, rname)
            try:
                fnames = os.listdir(rdir)
            except OSError:
                continue
            for fname in fnames:
                if fname.endswith(".dirty"):
                    info.dirty.append(os.path.join(rdir, fname))
                    continue
                if _CORRUPT_RE.match(fname):
                    info.quarantined.append(os.path.join(rdir, fname))
                    continue
                bm = _BLOCK_RE.match(fname)
                if bm:
                    it, owner, index, k, m = (int(g) for g in bm.groups())
                    fpath = os.path.join(rdir, fname)
                    try:
                        size = os.path.getsize(fpath)
                    except OSError:
                        continue
                    info.blocks.setdefault(it, {}).setdefault(
                        owner, {}
                    ).setdefault(index, set()).add(holder)
                    info.block_k[(it, owner)] = k
                    info.bytes_by_iter[it] = info.bytes_by_iter.get(it, 0) + size
                    info.block_files.append((fpath, holder, it, owner, index))
                    continue
                fm = _FILE_RE.match(fname)
                if not fm:
                    continue
                fpath = os.path.join(rdir, fname)
                try:
                    size = os.path.getsize(fpath)
                except OSError:
                    continue  # pruned mid-scan
                it, owner = int(fm.group(1)), int(fm.group(2))
                info.holdings.setdefault(it, {}).setdefault(owner, set()).add(holder)
                info.bytes_by_iter[it] = info.bytes_by_iter.get(it, 0) + size
                info.files.append((fpath, holder, it, owner))
        sessions.append(info)
    return sorted(sessions, key=lambda s: s.session)


def render(info: SessionInfo, out=None, world: Optional[set] = None) -> None:
    out = sys.stdout if out is None else out
    audit_world = sorted((info.ranks | info.owners) if world is None else world)
    covered = info.covered_iterations(set(audit_world))
    cold_note = (
        f", {len(info.cold)} in cold tier" if info.cold else ""
    )
    print(
        f"session {info.session}: auditing world={audit_world} "
        f"({len(info.holdings)} iterations on disk{cold_note})",
        file=out,
    )
    for it in sorted(set(info.holdings) | set(info.blocks) | set(info.cold)):
        by_owner = info.holdings.get(it, {})
        recon = info.reconstructible(it)
        cold_owners = set(info.cold.get(it, ()))
        missing = sorted(set(audit_world) - set(by_owner) - recon - cold_owners)
        copies = sum(len(h) for h in by_owner.values())
        mb = info.bytes_by_iter.get(it, 0) / 1e6
        status = "COVERED" if it in covered else f"missing owners {missing}"
        mirrors = copies - len(by_owner)
        nblocks = sum(
            len(holders)
            for by_index in info.blocks.get(it, {}).values()
            for holders in by_index.values()
        )
        ec = (
            f", {nblocks} erasure blocks"
            f" (reconstructible: {sorted(recon)})" if nblocks else ""
        )
        cd = f", cold: {sorted(cold_owners)}" if cold_owners else ""
        print(
            f"  iter {it:7d}: owners {sorted(by_owner)}, "
            f"{mirrors} mirror copies{ec}{cd}, {mb:.1f} MB  [{status}]",
            file=out,
        )
    if covered:
        print(
            f"  resumable from: iter {covered[-1]} (newest covered for "
            f"world {audit_world})",
            file=out,
        )
    else:
        print(
            f"  resumable from: NOTHING for world {audit_world}", file=out
        )
    if info.holdings:
        # Coverage is group-relative: after an elastic shrink, the surviving
        # group resumes from data the full world cannot. Name the group the
        # newest iteration WOULD serve, so a "NOTHING" verdict isn't misread.
        newest = max(info.holdings)
        owners = sorted(info.holdings[newest])
        if newest not in covered:
            print(
                f"  note: iter {newest} covers a (shrunk) world of {owners} — "
                f"re-audit with --world {','.join(map(str, owners))}",
                file=out,
            )
    for path in info.dirty:
        print(f"  WARNING torn save temp: {path}", file=out)
    for path in info.quarantined:
        print(f"  WARNING quarantined corrupt container: {path}", file=out)


def verify(sessions: list[SessionInfo], out=None, cold=None) -> int:
    """Stream-verify every container (and erasure block artifact) in
    ``sessions`` (bounded memory, one line per file); returns the number of
    corrupt files. v3 container verdicts are chunk-granular: a corrupt file
    names the exact ``leaf/chunk`` that failed, an intact one reports its
    manifest geometry. With ``cold`` (``{session: ColdTier}``, the ``--cold``
    wiring) every archived artifact is additionally checked against its cold
    manifest's whole-file digest."""
    from tpu_resiliency.checkpoint import format as ckpt_format
    from tpu_resiliency.checkpoint.coding import strategy as ckpt_coding
    from tpu_resiliency.exceptions import CheckpointError

    out = sys.stdout if out is None else out
    counts = {"ok": 0, "unverified": 0, "corrupt": 0}
    for info in sessions:
        print(
            f"session {info.session}: verifying {len(info.files)} "
            f"container(s), {len(info.block_files)} erasure block(s)",
            file=out,
        )
        for path, holder, it, owner in sorted(info.files):
            status, detail = ckpt_format.verify_file(path)
            counts[status] += 1
            print(f"  [{status.upper():10s}] {path}: {detail}", file=out)
        for path, holder, it, owner, index in sorted(info.block_files):
            try:
                with open(path, "rb") as f:
                    header, block = ckpt_coding.parse_block(f.read(), source=path)
                status, detail = "ok", (
                    f"block {header['index']} of k={header['k']} m={header['m']} "
                    f"(owner {header['owner']}, {block.nbytes} bytes)"
                )
            except (CheckpointError, OSError) as e:
                status, detail = "corrupt", str(e)
            counts[status] += 1
            print(f"  [{status.upper():10s}] {path}: {detail}", file=out)
        tier = (cold or {}).get(info.session)
        if tier is not None:
            mans = tier.manifests()
            narts = sum(len(per) for per in mans.values())
            print(
                f"session {info.session}: verifying {narts} cold "
                f"artifact(s)",
                file=out,
            )
            for it in sorted(mans):
                for owner in sorted(mans[it]):
                    status, detail = tier.verify(it, owner)
                    counts[status] += 1
                    print(
                        f"  [{status.upper():10s}] cold "
                        f"s{info.session}/iter {it} owner {owner}: {detail}",
                        file=out,
                    )
    print(
        f"verified: {counts['ok']} ok, {counts['unverified']} unverified, "
        f"{counts['corrupt']} corrupt",
        file=out,
    )
    return counts["corrupt"]


def render_chunks(sessions: list[SessionInfo], out=None) -> int:
    """The ``--chunks`` view: per container, the chunk manifest geometry and
    every failing chunk's (leaf, chunk) coordinates — what an operator reads
    before deciding whether a damaged shard is worth a ranged repair. Exit 1
    on any bad chunk or manifest-less corrupt file."""
    from tpu_resiliency.checkpoint import format as ckpt_format

    out = sys.stdout if out is None else out
    bad_files = 0
    for info in sessions:
        print(
            f"session {info.session}: chunk manifests for {len(info.files)} "
            f"container(s)",
            file=out,
        )
        for path, holder, it, owner in sorted(info.files):
            rep = ckpt_format.chunk_report(path)
            if rep["chunk_size"] is None:
                tag = "NO-MANIFEST"
                if rep["status"] == "corrupt":
                    bad_files += 1
                    tag = "CORRUPT"
                print(
                    f"  [{tag}] {path}: {rep['detail']} "
                    f"(no verifiable manifest — whole-file verdict only)",
                    file=out,
                )
                continue
            nchunks = sum(leaf["chunks"] for leaf in rep["leaves"])
            bad = [
                (li, c)
                for li, leaf in enumerate(rep["leaves"])
                for c in leaf["bad"]
            ]
            if bad:
                bad_files += 1
                print(
                    f"  [CORRUPT] {path}: {len(bad)}/{nchunks} chunk(s) bad "
                    f"@ {rep['chunk_size']} B: "
                    + ", ".join(f"leaf {li} chunk {c}" for li, c in bad[:8])
                    + (" ..." if len(bad) > 8 else ""),
                    file=out,
                )
            else:
                print(
                    f"  [OK] {path}: {nchunks} chunk(s) @ "
                    f"{rep['chunk_size']} B across {len(rep['leaves'])} "
                    f"leaves, all verified",
                    file=out,
                )
    return 1 if bad_files else 0


def render_plan(
    info: SessionInfo,
    world: set,
    axes: Optional[dict] = None,
    iteration: Optional[int] = None,
    out=None,
) -> int:
    """Compute and render the reshard plan for ``world`` against the newest
    layout-bearing iteration (or ``iteration``); returns the exit code (1 on
    uncovered ranges or no plannable iteration). Header reads only — no
    tensor bytes are touched."""
    from tpu_resiliency.checkpoint import format as ckpt_format
    from tpu_resiliency.checkpoint import reshard
    from tpu_resiliency.exceptions import CheckpointError

    out = sys.stdout if out is None else out
    target_ranks = sorted(world)
    candidates = sorted(info.holdings, reverse=True)
    if iteration is not None:
        candidates = [it for it in candidates if it == iteration]
    for it in candidates:
        # Any container of the iteration carries the full layout; take the first
        # readable one.
        source = None
        for path, holder, fit, owner in sorted(info.files):
            if fit != it:
                continue
            try:
                meta = ckpt_format.read_header(path).get("meta", {})
                source = reshard.extract_layout(meta)
            except CheckpointError:
                continue
            if source is not None:
                break
        if source is None:
            print(f"iter {it}: no readable layout-bearing container", file=out)
            continue
        try:
            target = source.retarget(target_ranks, axes=axes)
            plan = reshard.build_plan(source, target)
        except CheckpointError as e:
            print(f"iter {it}: cannot plan — {e}", file=out)
            return 1
        available = set(info.holdings[it])
        local_owners = {
            r: {
                o
                for o, holders in info.holdings[it].items()
                if r in holders
            }
            for r in target_ranks
        }
        print(
            f"session {info.session} iter {it}: reshard plan "
            f"{plan.source.world_size} -> {plan.target.world_size} ranks "
            f"({plan.direction}), source axes {dict(plan.source.axes)} -> "
            f"target axes {dict(plan.target.axes)}",
            file=out,
        )
        for r in target_ranks:
            rp = plan.for_rank(r)
            held = local_owners.get(r, set())
            print(
                f"  target rank {r}: {len(rp.segments)} cell(s), "
                f"{rp.nbytes} bytes",
                file=out,
            )
            for seg in rp.segments:
                via = (
                    "local" if set(seg.owners) & held
                    else ("peer-fetch" if set(seg.owners) & available
                          else "UNCOVERED")
                )
                spans = ", ".join(
                    f"[{rg.src_off}+{rg.nbytes})->[{rg.dst_off})"
                    for rg in seg.ranges[:4]
                )
                if len(seg.ranges) > 4:
                    spans += f", ... {len(seg.ranges) - 4} more"
                print(
                    f"    leaf {seg.leaf}: owners {list(seg.owners)} "
                    f"{seg.nbytes} B via {via}  {spans}",
                    file=out,
                )
        summary = plan.summary(local_owners=local_owners)
        print(
            f"  split: {summary['local_bytes']} B local, "
            f"{summary['peer_bytes']} B peer-fetched, "
            f"{summary['ranges']} range(s)",
            file=out,
        )
        missing = plan.missing_sources(available)
        if missing:
            names = sorted({r for rs in missing.values() for r in rs})
            print(
                f"  UNCOVERED: no surviving copy of source rank(s) {names} "
                f"(leaves {sorted(missing)})",
                file=out,
            )
            return 1
        print(f"  coverage: OK for world {target_ranks}", file=out)
        return 0
    print(
        "no plannable iteration (no containers carry reshard layout meta — "
        "save with save(..., layout=...))",
        file=out,
    )
    return 1


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Audit a tpu-resiliency local-checkpoint root offline"
    )
    def world_spec(text: str) -> set:
        try:
            out = {int(r) for r in text.split(",") if r.strip()}
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"want comma-separated rank ids, got {text!r}"
            )
        if not out:
            raise argparse.ArgumentTypeError("empty world")
        return out

    ap.add_argument("root")
    ap.add_argument("--session", type=int, help="only this session id")
    ap.add_argument(
        "--world",
        type=world_spec,
        help="audit coverage for this comma-separated rank set (default: every "
        "rank/owner the filesystem shows — the original full world)",
    )
    ap.add_argument(
        "--verify",
        action="store_true",
        help="stream-verify every container's checksums (per-leaf CRCs, v3 "
        "chunk manifests, trailer digest) and every erasure block artifact; "
        "print per-file verdicts; exit 1 on any mismatch",
    )
    ap.add_argument(
        "--cold",
        metavar="DIR",
        help="also scan this cold-tier object-store root (the launcher's "
        "--cold-dir): archived owners join the per-iteration coverage "
        "ledger as a third rung, cold-only sessions become auditable from "
        "an empty workdir, and --verify re-checks every archived artifact "
        "against its cold manifest digest",
    )
    ap.add_argument(
        "--chunks",
        action="store_true",
        help="render per-container chunk-manifest verdicts (chunk size, "
        "chunk count, exact (leaf, chunk) coordinates of any corruption); "
        "exit 1 on any bad chunk",
    )

    def axes_spec(text: str) -> dict:
        out = {}
        try:
            for part in text.split(","):
                if not part.strip():
                    continue
                name, size = part.split("=")
                out[name.strip()] = int(size)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"want name=size[,name=size...], got {text!r}"
            )
        if not out:
            raise argparse.ArgumentTypeError("empty axes spec")
        return out

    ap.add_argument(
        "--plan",
        action="store_true",
        help="render the elastic reshard plan for the --world target ranks "
        "(per-target-rank source cells, byte ranges, local vs peer-fetch "
        "split) without loading tensors; exit 1 if any range is uncovered",
    )
    ap.add_argument(
        "--axes",
        type=axes_spec,
        default=None,
        help="target mesh split for --plan, e.g. dp=2,tp=2 (default: the "
        "source layout with dp rescaled to the --world size)",
    )
    ap.add_argument(
        "--iteration", type=int, default=None,
        help="plan against this iteration (default: newest layout-bearing)",
    )
    args = ap.parse_args(argv)
    world = args.world
    if args.plan and world is None:
        print("--plan requires --world (the target rank set)", file=sys.stderr)
        return 2
    if not os.path.isdir(args.root):
        print(f"not a checkpoint root: {args.root}", file=sys.stderr)
        return 1
    sessions = scan(args.root, session=args.session)
    cold_tiers = {}
    if args.cold:
        if not os.path.isdir(args.cold):
            print(f"not a cold-tier root: {args.cold}", file=sys.stderr)
            return 1
        from tpu_resiliency.checkpoint.coldtier import (
            ColdTier,
            FilesystemStore,
        )

        store = FilesystemStore(args.cold)
        cold_ids = set()
        for key in store.list():
            km = re.match(r"^s(\d+)/", key)
            if km:
                cold_ids.add(int(km.group(1)))
        for sid in sorted(cold_ids):
            if args.session is not None and sid != args.session:
                continue
            tier = ColdTier(store, session=sid)
            coverage = tier.coverage()
            if not coverage:
                continue  # keys but no valid manifest: nothing trustworthy
            cold_tiers[sid] = tier
            for info in sessions:
                if info.session == sid:
                    info.cold = coverage
                    break
            else:
                # Cold-only session — the restore-anywhere case: an empty
                # (or freshly provisioned) workdir still audits what a new
                # job could bootstrap from the object store.
                stub = SessionInfo(sid, set(), {}, {}, [])
                stub.cold = coverage
                sessions.append(stub)
        sessions.sort(key=lambda s: s.session)
    if not sessions:
        print("no sessions found", file=sys.stderr)
        return 1
    if args.plan:
        rc = [0]

        def emit_plan():
            # One session per plan render (pass --session to disambiguate).
            rc[0] = max(
                render_plan(
                    info, world, axes=args.axes, iteration=args.iteration
                )
                for info in sessions
            )

        if pipe_safe(emit_plan):
            return SIGPIPE_EXIT
        return rc[0]
    if args.verify:
        corrupt = [0]

        def emit_verify():
            corrupt[0] = verify(sessions, cold=cold_tiers)

        if pipe_safe(emit_verify):
            return SIGPIPE_EXIT
        return 1 if corrupt[0] else 0
    if args.chunks:
        rc_c = [0]

        def emit_chunks():
            rc_c[0] = render_chunks(sessions)

        if pipe_safe(emit_chunks):
            return SIGPIPE_EXIT
        return rc_c[0]

    def emit():
        for info in sessions:
            render(info, world=world)

    if pipe_safe(emit):
        return SIGPIPE_EXIT
    return 0


if __name__ == "__main__":
    sys.exit(main())
