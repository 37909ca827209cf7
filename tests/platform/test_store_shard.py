"""Sharded store clique (platform/shardstore.py): the client-side keyspace
partition must be invisible to every caller of the KVClient surface — keyed
ops route deterministically, fan-out ops merge losslessly, barriers and
watch-parks stay shard-local, and the aggregated store_stats document folds
the shards into one view with the shard map attached."""

import threading

import pytest

from tpu_resiliency.exceptions import BarrierOverflow, StoreTimeoutError
from tpu_resiliency.platform.shardstore import (
    CliqueStore,
    LocalClique,
    ShardedKVClient,
    connect_store,
    format_endpoints,
    parse_endpoints,
    shard_of,
)


@pytest.fixture
def clique():
    c = LocalClique(3)
    yield c
    c.close()


@pytest.fixture
def client(clique):
    c = ShardedKVClient(clique.endpoints, timeout=30.0)
    yield c
    c.close()


def test_shard_of_is_deterministic_and_spread():
    # Stable across calls (crc32, not salted hash()) and actually spreading.
    keys = [f"jobmetrics/default/{i}" for i in range(256)]
    first = [shard_of(k, 4) for k in keys]
    assert first == [shard_of(k, 4) for k in keys]
    assert len(set(first)) == 4  # all shards hit at 256 keys
    assert all(shard_of(k, 1) == 0 for k in keys)


def test_endpoint_spec_roundtrip():
    eps = [("127.0.0.1", 1000), ("10.0.0.2", 29511)]
    assert parse_endpoints(format_endpoints(eps)) == eps
    with pytest.raises(ValueError):
        parse_endpoints("  ,  ")


def test_keyed_ops_route_and_read_back(client, clique):
    # Keys land on exactly the shard the hash names — and only there.
    for i in range(32):
        client.set(f"k/{i}", i)
    assert len(client.prefix_get("k/")) == 32
    for i in range(32):
        owner = shard_of(f"k/{i}", 3)
        for si, srv in enumerate(clique.servers):
            held = f"k/{i}" in srv._data
            assert held == (si == owner), (i, si, owner)
    assert client.get("k/7", timeout=1.0) == 7
    assert client.add("ctr", 5) == 5
    ok, val = client.compare_set("cas", None, "v1")
    assert ok and client.get("cas", timeout=1.0) == "v1"
    assert client.delete("k/7") is True
    assert client.try_get("k/7", "gone") == "gone"


def test_fanout_ops_merge_across_shards(client):
    for i in range(24):
        client.set(f"m/{i}", i)
        client.touch(f"hb/{i}")
    client.list_append("l/x", 1)
    client.set_add("s/x", [1, 2])
    assert client.num_keys() == 24 + 24  # values + touch stamps (lists/sets live apart)
    assert len(client.prefix_get("m/")) == 24
    assert client.keys("m/") == sorted(f"m/{i}" for i in range(24))
    assert client.check([f"m/{i}" for i in range(24)])
    assert not client.check(["m/0", "m/nope"])
    assert client.stale_keys("hb/", max_age=3600.0) == {}
    assert client.prefix_clear("m/") == 24
    assert client.prefix_get("m/") == {}


def test_barrier_is_shard_local_and_released(client, clique):
    world = 4
    name = "elastic/round"
    owner = shard_of(name, 3)
    released = []

    def join(rank):
        client.barrier_join(name, rank, world, timeout=30.0)
        released.append(rank)

    threads = [threading.Thread(target=join, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert sorted(released) == list(range(world))
    # The barrier object lives on exactly the shard the name hashes to.
    for si, srv in enumerate(clique.servers):
        assert (name in srv._barriers) == (si == owner)
    # Census fans out and still finds it (by name, wherever it lives).
    assert name in client.barrier_names()
    st = client.barrier_status(name)
    assert st is not None and st["generation"] == 1
    # Overflow semantics intact through the shard route.
    client.barrier_join(name, 0, world, timeout=0.0, wait=False)
    with pytest.raises(BarrierOverflow):
        client.barrier_join(name, 0, world, timeout=0.0)


def test_parked_wait_wakes_through_the_shard(client):
    got = []

    def waiter():
        got.append(client.get("park/me", timeout=10.0))

    t = threading.Thread(target=waiter)
    t.start()
    client.set("park/me", "woken")
    t.join(10.0)
    assert got == ["woken"]
    with pytest.raises(StoreTimeoutError):
        client.get("park/never", timeout=0.05)


def test_store_stats_aggregates_shards(client, clique):
    for i in range(64):
        client.set(f"st/{i}", i)
    doc = client.store_stats()
    assert doc["enabled"] is True
    assert doc["backend"] == "epoll"
    assert doc["aggregate_of"] == 3
    assert doc["shard_map"]["nshards"] == 3
    assert doc["shard_map"]["hash"] == "crc32"
    assert len(doc["shards"]) == 3
    assert len(doc["shard_map"]["endpoints"]) == 3
    # Sampled tallies: the sum over shards accounts the storm's volume.
    assert sum(s["keys"] for s in doc["shards"]) == client.num_keys()
    # Every shard served some of the spread keyspace.
    assert all(s["backend"] == "epoll" for s in doc["shards"])


def test_two_shards_share_a_1024_rank_keyspace():
    """The per-shard balance, as an exact count: one key a rank for 1,024 ranks
    over a two-shard clique lands on both shards, the busier holding under
    three quarters (1/2 is perfect, 1.0 one loop serving it all)."""
    two = LocalClique(2)
    c = ShardedKVClient(two.endpoints, timeout=30.0)
    try:
        for rank in range(1024):
            c.set(f"storm/{rank}", rank)
        keys = [s["keys"] for s in c.store_stats()["shards"]]
        assert len(keys) == 2 and sum(keys) == 1024 and min(keys) > 0
        assert max(keys) / sum(keys) < 0.75
    finally:
        c.close()
        two.close()


def test_clique_store_view_and_factory(clique, monkeypatch):
    cs = CliqueStore(clique.endpoints, prefix="ns/")
    try:
        cs.set("a", 1)
        assert cs.prefix_get("") == {"a": 1}
    finally:
        cs.close()
    # Factory: an explicit spec (or the env) yields a sharded view; a
    # 1-endpoint spec degenerates to the classic CoordStore.
    from tpu_resiliency.platform.shardstore import SHARDS_ENV
    from tpu_resiliency.platform.store import CoordStore

    st = connect_store("ignored", 1, shards=clique.spec)
    try:
        assert isinstance(st.client, ShardedKVClient)
        st.set("b", 2)
        assert st.get("b", timeout=1.0) == 2
    finally:
        st.close()
    one = format_endpoints(clique.endpoints[:1])
    st1 = connect_store("ignored", 1, shards=one)
    try:
        assert isinstance(st1, CoordStore)
        assert st1.client.port == clique.endpoints[0][1]
    finally:
        st1.close()
    monkeypatch.setenv(SHARDS_ENV, clique.spec)
    st2 = connect_store("127.0.0.1", clique.endpoints[0][1])
    try:
        assert isinstance(st2.client, ShardedKVClient)
        assert st2.get("b", timeout=1.0) == 2  # same keyspace as st
    finally:
        st2.close()


def test_dead_shard_fails_fast_not_silently(clique):
    """One dead shard: keyed ops against IT surface transport errors after
    that shard's own retry budget; keyed ops against live shards keep
    working; the aggregated stats degrade the dead shard's row only."""
    from tpu_resiliency.exceptions import StoreError

    c = ShardedKVClient(clique.endpoints, timeout=5.0, retry_budget=0.3)
    try:
        dead = 1
        clique.servers[dead].close()
        live_key = next(
            f"x/{i}" for i in range(64) if shard_of(f"x/{i}", 3) != dead
        )
        dead_key = next(
            f"x/{i}" for i in range(64) if shard_of(f"x/{i}", 3) == dead
        )
        c.set(live_key, "ok")
        assert c.get(live_key, timeout=1.0) == "ok"
        with pytest.raises(StoreError):
            c.set(dead_key, "nope")
        doc = c.store_stats()
        assert doc["enabled"] is True  # live shards still answer
        rows = {s["endpoint"]: s for s in doc["shards"]}
        dead_ep = f"{clique.endpoints[dead][0]}:{clique.endpoints[dead][1]}"
        assert rows[dead_ep]["enabled"] is False
        assert rows[dead_ep]["backend"] == "unreachable"
        # A clique client must also be CONSTRUCTIBLE while a shard is down
        # (shard connections are lazy): live-shard ops work immediately, the
        # dead shard only fails the op that actually routes to it.
        late = ShardedKVClient(
            clique.endpoints, timeout=5.0, connect_retries=1,
            retry_budget=0.3,
        )
        try:
            late.set(live_key, "still-ok")
            assert late.get(live_key, timeout=1.0) == "still-ok"
            with pytest.raises(StoreError):
                late.get(dead_key, timeout=0.1)
            assert late.store_stats()["enabled"] is True
        finally:
            late.close()
    finally:
        c.close()


def test_parallel_fanout_merge_is_order_independent(clique, client):
    """The prefix/scan/census fan-out runs shards CONCURRENTLY now: whatever
    order shards answer in, the merged result must be identical to the
    serial-era merge (disjoint keyspaces make this structural — this test
    pins it against regressions in the merge code)."""
    import random

    keys = [f"fan/{i}" for i in range(96)]
    for k in keys:
        client.set(k, k.upper())

    # Reference: per-shard serial merges in every shard permutation.
    per_shard = [
        clique.client().client._shard(i).prefix_get("fan/")
        for i in range(len(clique.endpoints))
    ]
    for perm in ([0, 1, 2], [2, 1, 0], [1, 2, 0]):
        merged = {}
        for i in perm:
            merged.update(per_shard[i])
        assert merged == client.prefix_get("fan/")

    # keys()/num_keys() agree with the merged view.
    assert client.keys("fan/") == sorted(merged)
    assert client.num_keys() >= len(keys)
    # Repeated concurrent fan-outs are stable (no racy partial merges).
    snap = client.prefix_get("fan/")
    for _ in range(8):
        assert client.prefix_get("fan/") == snap
    # And a keyed op mid-fan-out cannot corrupt the merge: clear returns the
    # exact number of keys the merged view showed.
    assert client.prefix_clear("fan/") == len(merged)
    assert client.prefix_get("fan/") == {}
