"""The delta rule's share of its roofline: the larger of operations over the chip's bf16
peak and bytes over its HBM bandwidth (``peaks.json``) for the rule of every delta layer,
forward and backward, at the least the algorithm needs
(``families/solar.py:delta_rule_cost``: the Gram matrices, the solve and the products with
the state by chunks, no recomputation; q, k, v, the log-decays, the output, their
cotangents and the state at each chunk's start once), over the device time of the ops under
``attn/full/delta/rule`` in one step, in %. Read by scope and not by a kernel's name, so it
reads the same work whatever computes the rule
(``layer_metrics/attn.delta_ms.py:times``). Nothing where the program has no such scope,
the family counts no rule, the device is not in ``peaks.json`` or there is no trace."""

from benchmark import harness


def read(run):
    found = harness.load_by_path("layer_metrics", "attn.delta_ms").times(run)
    config = run.cell.config
    peaks = harness.read_json(harness.HERE, "peaks.json")["device_kinds"].get(run.device["kind"])
    count = getattr(harness.load_family(config), "delta_rule_cost", None)
    if not found or not found["rule"] or peaks is None or count is None:
        return None
    ops, moved = count(config, *config["batch"])
    floor = max(ops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    run.say("delta_rule", rule_ms=found["rule"] * 1e3, operations=ops, bytes=moved,
            floor_ms=floor * 1e3)
    return 100.0 * floor / found["rule"]
