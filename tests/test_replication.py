"""core.replication: peer-replica repair of a lost ShardedBackend host,
and the coordinator's loud-commit contract (a manifest referencing lost
writes is refused, never published silently partial)."""
import shutil

import numpy as np
import pytest

from repro.core import (CheckpointManager, Incarnation, OpLog,
                        ShardedBackend, UpperHalf, replication)
from repro.core.backends.sharded import _host_of


def _mk_upper(seed=0, n=60_000):
    rng = np.random.RandomState(seed)
    up = UpperHalf()
    up.register("params", "params",
                {"w": rng.randn(n).astype(np.float32),
                 "b": rng.randn(256).astype(np.float32)})
    up.register("step", "step", np.int64(seed))
    return up


def _blob_census(be: ShardedBackend):
    """{host: set(blob filenames)} across the store."""
    out = {}
    for h in range(be.n_hosts):
        d = be.root / f"host_{h:03d}"
        out[h] = set(p.name for p in d.iterdir()) if d.is_dir() else set()
    return out


# --- repair ------------------------------------------------------------------

def test_repair_rebuilds_lost_host_from_peers(tmp_path):
    """fail_host(h) + delete host_h's directory: repair() restores every
    blob the host held — owned primaries from the (h+1)%N replicas,
    held replicas from the (h-1)%N primaries — byte-identically."""
    be = ShardedBackend(str(tmp_path), n_hosts=4, replicate=True)
    mgr = CheckpointManager(be, async_save=False)
    up = _mk_upper(1)
    mgr.save(1, up, OpLog())
    before = _blob_census(be)
    lost = 2
    assert before[lost], "host 2 must own something for the test to bite"
    data_before = {name: (be.root / f"host_{lost:03d}" / name).read_bytes()
                   for name in before[lost]}

    be.fail_host(lost)
    shutil.rmtree(be.root / f"host_{lost:03d}")

    rep = replication.repair(be, host=lost)
    assert rep.restored == len(before[lost])
    assert not rep.unrecoverable
    after = _blob_census(be)
    assert after == before
    for name, want in data_before.items():
        got = (be.root / f"host_{lost:03d}" / name).read_bytes()
        assert got == want
    # healed: reads hit the primary again, and a fresh scan is clean
    assert lost not in be._failed_hosts
    assert not replication.scan(be).degraded


def test_repair_then_incarnation_restore(tmp_path):
    """The supervisor's sequence: lose a host wholesale, repair from
    peers, then a full Incarnation restore over the repaired store
    succeeds bit-identically (replicate=True)."""
    be = ShardedBackend(str(tmp_path), n_hosts=3, replicate=True)
    mgr = CheckpointManager(be, async_save=False)
    up = _mk_upper(2)
    want = np.array(up.get("params")["w"])
    mgr.save(5, up, OpLog())

    be.fail_host(0)
    shutil.rmtree(be.root / "host_000")
    replication.repair(be, host=0)

    inc = Incarnation(mgr)
    state = inc.materialize()
    np.testing.assert_array_equal(state.entries["params"]["['w']"], want)
    assert int(inc.scalar("step")) == 2


def test_scan_reports_degradation_and_unrecoverable(tmp_path):
    """scan() is read-only truth: missing copies are counted, a blob
    with no surviving copy is named, and repair() reports (not hides)
    the unrecoverable ones."""
    be = ShardedBackend(str(tmp_path), n_hosts=4, replicate=True)
    mgr = CheckpointManager(be, async_save=False)
    mgr.save(1, _mk_upper(3), OpLog())
    assert not replication.scan(be).degraded

    # delete one primary: degraded but recoverable
    census = _blob_census(be)
    h, name = next((h, n) for h, names in census.items()
                   for n in names if not n.startswith("replica_"))
    (be.root / f"host_{h:03d}" / name).unlink()
    rep = replication.scan(be)
    assert rep.missing_primaries == 1 and not rep.unrecoverable

    # delete its replica too: unrecoverable, and repair says so
    r = (h + 1) % be.n_hosts
    (be.root / f"host_{r:03d}" / f"replica_{name}").unlink()
    rep = replication.repair(be)
    assert rep.unrecoverable == [name]


def test_repair_without_replication_cannot_invent_data(tmp_path):
    """replicate=False: a lost host's blobs have no peer copy — repair
    reports every one unrecoverable instead of pretending."""
    be = ShardedBackend(str(tmp_path), n_hosts=3, replicate=False)
    mgr = CheckpointManager(be, async_save=False)
    mgr.save(1, _mk_upper(4), OpLog())
    lost_names = _blob_census(be)[1]
    assert lost_names
    shutil.rmtree(be.root / "host_001")
    rep = replication.repair(be, host=1)
    assert set(rep.unrecoverable) == lost_names
    assert rep.restored == 0


# --- loud commit -------------------------------------------------------------

def test_commit_refuses_manifest_with_lost_writes(tmp_path):
    """The regression the docstring promised: if a host's writes were
    lost between blob write and manifest commit, the coordinator must
    refuse the commit — the store keeps its previous 'latest', never a
    checkpoint it cannot serve."""
    be = ShardedBackend(str(tmp_path), n_hosts=4, replicate=False)
    mgr = CheckpointManager(be, async_save=False)
    mgr.save(1, _mk_upper(5), OpLog())

    m = be.get_manifest(1)
    # simulate losing one referenced blob's host directory wholesale
    from repro.core.delta import referenced_hashes
    name = sorted(referenced_hashes(m))[0]
    shutil.rmtree(be.root / f"host_{_host_of(name, be.n_hosts):03d}")
    with pytest.raises(RuntimeError, match="unservable"):
        be.commit_manifest(2, m)
    assert be.list_steps() == [1]        # nothing partial published


def test_put_blob_to_down_host_raises(tmp_path):
    """A down host's writer cannot 'succeed': the write is lost and the
    pipeline must hear about it before the manifest publishes."""
    be = ShardedBackend(str(tmp_path), n_hosts=2, replicate=False)
    name = "aaaa"                        # find a name owned by host 1
    while _host_of(name, 2) != 1:
        name += "a"
    be.fail_host(1)
    with pytest.raises(IOError, match="host 1 down"):
        be.put_blob(name, b"payload")
    be.heal_host(1)
    be.put_blob(name, b"payload")        # healed writer lands it
    assert be.get_blob(name) == b"payload"


def test_restore_with_dead_peer_matches_healthy(tmp_path):
    """Restore under degradation: with one host of the ring dead
    (replicate=True, so every blob keeps a surviving copy) the restore
    equals the restore of the healthy store bit for bit — ``get_blob``
    reads around the dead peer from the surviving placement, it does
    not relax correctness."""
    be = ShardedBackend(str(tmp_path), n_hosts=3, replicate=True)
    mgr = CheckpointManager(be, async_save=False)
    up = _mk_upper(7)
    rng = np.random.RandomState(77)
    up.register("opt_state", "opt_state",
                {"m": rng.randn(4096).astype(np.float32)})
    mgr.save(1, up, OpLog())

    healthy = mgr.restore(1)
    be.fail_host(1)
    # a read served by the dead host would now decode to other values
    dead = [p for p in (tmp_path / "host_001").iterdir() if p.is_file()]
    assert dead, "host 1 must hold blobs for the failure to bite"
    for p in dead:
        p.write_bytes(b"\xff" * p.stat().st_size)
    degraded = mgr.restore(1)
    assert set(degraded.entries) == set(healthy.entries)
    for name, by_path in healthy.entries.items():
        got = degraded.entries[name]
        assert set(got) == set(by_path)
        for path, want in by_path.items():
            np.testing.assert_array_equal(np.asarray(got[path]),
                                          np.asarray(want))


def test_scan_cli_json_contract(tmp_path, capsys):
    """``python -m repro.core.replication STORE --json``: the emitted
    JSON carries every report field plus the derived verdict, and the
    exit code is the health bit (0 healthy, 1 degraded) so the CLI
    works as an operator probe."""
    import json

    spec = f"sharded:{tmp_path}?hosts=3&replicate=1"
    be = ShardedBackend(str(tmp_path), n_hosts=3, replicate=True)
    mgr = CheckpointManager(be, async_save=False)
    mgr.save(1, _mk_upper(8), OpLog())

    rc = replication.main([spec, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(rep) == {"hosts", "blobs", "missing_primaries",
                        "missing_replicas", "restored", "unrecoverable",
                        "degraded"}
    assert rep["degraded"] is False and rep["blobs"] > 0

    census = _blob_census(be)
    h, name = next((h, n) for h, names in census.items()
                   for n in names if not n.startswith("replica_"))
    (be.root / f"host_{h:03d}" / name).unlink()
    rc = replication.main([spec, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert rep["degraded"] is True and rep["missing_primaries"] == 1

    rc = replication.main([spec, "--repair", "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["restored"] == 1


def test_scan_cli_rejects_non_sharded_store(tmp_path, capsys):
    """A localfs store has no replicas to scan — the CLI says so on
    stderr and exits 2 (usage), instead of reporting fake health."""
    rc = replication.main([f"localfs:{tmp_path}", "--json"])
    err = capsys.readouterr().err
    assert rc == 2 and "sharded" in err


def test_save_through_manager_fails_loudly_on_down_host(tmp_path):
    """End-to-end: a snapshot through the async pipeline with a down
    (unreplicated) host raises at save time and publishes nothing."""
    be = ShardedBackend(str(tmp_path), n_hosts=2, replicate=False)
    mgr = CheckpointManager(be, async_save=False)
    be.fail_host(1)
    with pytest.raises(IOError, match="down"):
        mgr.save(1, _mk_upper(6), OpLog())
    assert be.list_steps() == []
