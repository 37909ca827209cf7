import pytest

from tpu_resiliency.exceptions import FaultToleranceError
from tpu_resiliency.watchdog import HeartbeatTimeouts, TimeoutsCalc


def test_hb_gap_tracking_injected_times():
    """Injected timestamps, no sleeping (reference test_timeouts_calc.py pattern)."""
    calc = TimeoutsCalc(safety_factor=5.0)
    calc.start_time = 100.0
    calc.update_on_heartbeat(103.0)  # initial gap 3
    calc.update_on_heartbeat(104.0)  # subsequent 1
    calc.update_on_heartbeat(106.5)  # subsequent 2.5
    t = calc.get_hb_timeouts()
    assert t.initial == pytest.approx(5.0 * 3.0)
    assert t.subsequent == pytest.approx(5.0 * 2.5)
    assert t.calculated


def test_initial_timeout_covers_subsequent_gap():
    calc = TimeoutsCalc(safety_factor=2.0)
    calc.start_time = 0.0
    calc.update_on_heartbeat(1.0)
    calc.update_on_heartbeat(11.0)  # subsequent gap 10 > initial gap 1
    t = calc.get_hb_timeouts()
    assert t.initial == pytest.approx(20.0)


def test_needs_two_heartbeats():
    calc = TimeoutsCalc()
    calc.start_time = 0.0
    calc.update_on_heartbeat(1.0)
    with pytest.raises(FaultToleranceError):
        calc.get_hb_timeouts()


def test_ema_merge_with_previous():
    calc = TimeoutsCalc(safety_factor=1.0)
    calc.start_time = 0.0
    calc.update_on_heartbeat(4.0)
    calc.update_on_heartbeat(6.0)
    prev = HeartbeatTimeouts(initial=8.0, subsequent=4.0, calculated=True)
    t = calc.get_hb_timeouts(previous=prev)
    assert t.initial == pytest.approx(0.5 * 4.0 + 0.5 * 8.0)
    assert t.subsequent == pytest.approx(0.5 * 2.0 + 0.5 * 4.0)


def test_sections():
    calc = TimeoutsCalc(safety_factor=2.0)
    calc.update_on_section_open("step", 10.0)
    calc.update_on_section_close("step", 11.5)
    calc.update_on_section_open("step", 20.0)  # out-of-section gap 8.5
    calc.update_on_section_close("step", 21.0)
    st = calc.get_section_timeouts()
    assert st.section["step"] == pytest.approx(2.0 * 1.5)
    assert st.out_of_section == pytest.approx(2.0 * 8.5)
    with pytest.raises(FaultToleranceError):
        calc.update_on_section_close("never-opened")


def test_store_synchronize_max(kv_server):
    import threading

    from tpu_resiliency.platform.store import CoordStore

    world = 3
    results = {}

    def run(rank):
        store = CoordStore("127.0.0.1", kv_server.port)
        calc = TimeoutsCalc(safety_factor=1.0)
        calc.start_time = 0.0
        calc.update_on_heartbeat(1.0 + rank)  # rank 2 has largest initial gap 3
        calc.update_on_heartbeat(2.0 + rank * 2)  # rank 2: gap 3
        calc.synchronize_all(store, rank, world)
        results[rank] = calc.get_hb_timeouts()
        store.close()

    threads = [threading.Thread(target=run, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    # all ranks agree on the MAX-merged gaps
    assert results[0].initial == results[1].initial == results[2].initial
    assert results[0].initial == pytest.approx(3.0)
    assert results[0].subsequent == pytest.approx(3.0)


def test_store_synchronize_sections_max_contract(kv_server):
    """review round 3 Missing #6: the store-round section sync satisfies the
    reference's max-across-ranks contract (``timeouts_calc.py:74-91``): after
    ``synchronize_all`` every rank's section/out-of-section stats equal the
    element-wise MAX over ranks, all ranks produce IDENTICAL timeouts, and the
    contract holds across repeated sync epochs (reentrant barriers)."""
    import threading

    from tpu_resiliency.platform.store import CoordStore

    world = 4
    # rank r: step takes 1+r, ckpt takes 10-2r, out-of-section gap 0.5*r.
    step_d = {r: 1.0 + r for r in range(world)}
    ckpt_d = {r: 10.0 - 2 * r for r in range(world)}
    oos_d = {r: 0.5 * r for r in range(world)}
    results = {}
    errors = []

    def run(rank):
        try:
            store = CoordStore("127.0.0.1", kv_server.port)
            calc = TimeoutsCalc(safety_factor=2.0)
            t = 100.0
            calc.update_on_section_open("step", t)
            calc.update_on_section_close("step", t + step_d[rank])
            t += step_d[rank] + oos_d[rank]
            calc.update_on_section_open("ckpt", t)
            calc.update_on_section_close("ckpt", t + ckpt_d[rank])
            calc.synchronize_all(store, rank, world)
            merged_e1 = dict(calc.section_max_elapsed)
            oos_e1 = calc.out_of_section_max
            first = calc.get_section_timeouts()
            # Second epoch: a new, larger local observation on ONE rank must
            # propagate to every rank through a fresh sync round.
            if rank == 1:
                calc.update_on_section_open("step", 200.0)
                calc.update_on_section_close("step", 212.0)  # 12 s
            calc.synchronize_all(store, rank, world)
            second = calc.get_section_timeouts(previous=first)
            results[rank] = (first, second, merged_e1, oos_e1)
            store.close()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, repr(e)))

    threads = [threading.Thread(target=run, args=(r,)) for r in range(world)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60.0)
    assert not errors, errors
    assert set(results) == set(range(world))

    # Epoch 1: merged stats are the global max on EVERY rank.
    for rank, (first, _, merged, oos) in results.items():
        assert merged["step"] == pytest.approx(max(step_d.values()))  # 4.0
        assert merged["ckpt"] == pytest.approx(max(ckpt_d.values()))  # 10.0
        assert oos >= max(oos_d.values())
        assert first.section["step"] == pytest.approx(2.0 * 4.0)
        assert first.section["ckpt"] == pytest.approx(2.0 * 10.0)
        assert first.calculated_sections == frozenset({"step", "ckpt"})
    # All ranks computed identical timeouts (the synchronized-values contract).
    firsts = [results[r][0] for r in range(world)]
    assert all(f.section == firsts[0].section for f in firsts)
    assert all(f.out_of_section == firsts[0].out_of_section for f in firsts)

    # Epoch 2: rank 1's 12 s step observation reached everyone, and the EMA
    # merge with epoch-1 values matches the reference formula on every rank.
    seconds = [results[r][1] for r in range(world)]
    assert all(s.section == seconds[0].section for s in seconds)
    assert seconds[0].section["step"] == pytest.approx(0.5 * (2.0 * 12.0) + 0.5 * 8.0)
