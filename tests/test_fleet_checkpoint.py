"""Fleet session checkpoints (ISSUE-17, fleet/checkpoint.py, docs/FLEET.md):
tensor-level serialization of a warm solve lineage, crc32c-framed and
content-digested, restored on an adopting replica by ONE deserialize plus a
never-trust verify chain.

The contracts under test: the codec round-trips every plane bit for bit
across processes (hash randomization included); every possible file
truncation loads to a clean miss, never an exception; a stale checkpoint
downgrades to journal replay; and the restored lineage's NEXT solve is
bit-identical to an uninterrupted server's."""

import os
import subprocess
import sys

import numpy as np
import pytest

from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
from karpenter_core_tpu.fleet import FleetLocal, FleetMap
from karpenter_core_tpu.fleet import checkpoint as ckpt_mod
from karpenter_core_tpu.fleet.checkpoint import (
    FleetRestoreError,
    dec,
    enc,
    load_checkpoint,
)
from karpenter_core_tpu.service.snapshot_channel import (
    SnapshotSolverClient,
    serve,
)
from karpenter_core_tpu.service.tenant import TenantConfig
from karpenter_core_tpu.testing import make_pod, make_provisioner


def _loose_config(**kw) -> TenantConfig:
    base = dict(
        rate_per_s=1000.0, burst=1000, max_inflight=64,
        batch_window_s=0.0, max_batch=8,
        breaker_threshold=3, breaker_reset_s=30.0,
    )
    base.update(kw)
    return TenantConfig(**base)


def _fleet(tmp_path, rid="r1", ckpt_every=8) -> FleetLocal:
    return FleetLocal(
        directory=str(tmp_path / "fleet"),
        replica_id=rid,
        fleet_map=FleetMap.parse("r1=127.0.0.1:1,r2=127.0.0.1:2"),
        ckpt_every=ckpt_every,
    )


def _serve(provider, fleet=None, journal_dir=None):
    server, port = serve(
        provider, tenant_config=_loose_config(),
        journal_dir=str(journal_dir) if journal_dir else None,
        fleet=fleet,
    )
    return server, SnapshotSolverClient(f"127.0.0.1:{port}")


def _stop(server, client, abandon=False):
    client.close()
    server.stop(grace=0)
    svc = server.kc_service
    if svc.journal is not None:
        if abandon:
            svc.journal.abandon()
        else:
            svc.shutdown()


def _solve(client, tenant_id, count=4, version=0, cpu="500m"):
    return client.solve_tenant_classes(
        [(make_pod(requests={"cpu": cpu}), count)], [make_provisioner()],
        tenant={"id": tenant_id, "sessionVersion": version},
    )


def _counter_value(counter, **labels) -> float:
    total = 0.0
    for _name, sample_labels, value in counter.samples():
        if all(sample_labels.get(k) == v for k, v in labels.items()):
            total += value
    return total


# -- codec --------------------------------------------------------------------


class TestCodec:
    def test_ndarray_round_trip_exact(self):
        for arr in (
            np.arange(12, dtype=np.int32).reshape(3, 4),
            np.linspace(-1, 1, 7, dtype=np.float32),
            np.array([], dtype=np.float64),
            np.array(True),
        ):
            out = dec(enc(arr))
            assert out.dtype == arr.dtype and out.shape == arr.shape
            np.testing.assert_array_equal(out, arr)

    def test_scalar_and_container_round_trip(self):
        payload = {
            "i": 7, "f": 2.5, "s": "x", "b": b"\x00\xff", "n": None,
            "t": (1, (2, "three")), "l": [1, [2]],
            "np": np.float32(1.5),
            "map": {1: "int-key", ("tu", 2): "tuple-key"},
        }
        out = dec(enc(payload))
        assert out["t"] == (1, (2, "three"))
        assert out["map"] == {1: "int-key", ("tu", 2): "tuple-key"}
        assert out["np"] == np.float32(1.5)
        assert out["b"] == b"\x00\xff"

    def test_namedtuple_round_trip_and_unknown_class_refuses(self):
        from karpenter_core_tpu.ops.masks import ReqTensor

        rt = ReqTensor(
            mask=np.zeros((2, 3), dtype=bool),
            defined=np.ones((2, 3), dtype=bool),
            negative=np.zeros((2, 3), dtype=bool),
            gt=np.zeros((2, 3), dtype=np.float32),
            lt=np.zeros((2, 3), dtype=np.float32),
        )
        out = dec(enc(rt))
        assert type(out).__name__ == "ReqTensor"
        np.testing.assert_array_equal(out.defined, rt.defined)

        bogus = {"__kc__": "nt", "c": "os.system", "f": []}
        with pytest.raises(FleetRestoreError):
            dec(bogus)
        with pytest.raises(FleetRestoreError):
            dec({"__kc__": "no-such-tag"})


# -- file format --------------------------------------------------------------


def _checkpoint_after_anchor(tmp_path, tenant="acme", count=6):
    """One anchor solve on a fleet replica; returns (path, version)."""
    fleet = _fleet(tmp_path)
    server, client = _serve(FakeCloudProvider(), fleet=fleet)
    try:
        r = _solve(client, tenant, count=count)
        assert r["tenant"]["solveMode"] == "full"
        svc = server.kc_service
        path = svc._ckpt.path_for(tenant)
        assert os.path.exists(path), "anchor solves checkpoint immediately"
        return path, r["tenant"]["sessionVersion"]
    finally:
        _stop(server, client)


class TestCheckpointFile:
    def test_write_load_round_trip(self, tmp_path):
        path, version = _checkpoint_after_anchor(tmp_path)
        ckpt, status = load_checkpoint(path)
        assert status == ckpt_mod.STATUS_OK
        assert ckpt.version == version
        assert ckpt.header["tenant"] == "acme"
        assert isinstance(ckpt.anchor, bytes) and ckpt.anchor
        assert ckpt.state["version"] == version

    def test_every_byte_truncation_never_raises(self, tmp_path):
        """kill -9 mid-publish: any prefix of a checkpoint file loads to a
        clean miss (never an exception), and only the COMPLETE file loads
        OK — the digest trailer refuses every partial."""
        path, _ = _checkpoint_after_anchor(tmp_path, count=3)
        data = open(path, "rb").read()
        probe = str(tmp_path / "probe.kcfc")
        # every boundary plus a byte-level sweep of the head and tail (the
        # full byte sweep at tensor sizes would dominate tier-1 runtime)
        cuts = set(range(0, min(len(data), 256)))
        cuts.update(range(max(len(data) - 256, 0), len(data) + 1))
        cuts.update(np.linspace(0, len(data), 64, dtype=int).tolist())
        for cut in sorted(cuts):
            with open(probe, "wb") as f:
                f.write(data[:cut])
            ckpt, status = load_checkpoint(probe)
            if cut == len(data):
                assert status == ckpt_mod.STATUS_OK and ckpt is not None
            else:
                assert ckpt is None, f"cut at {cut} produced a checkpoint"
                assert status != ckpt_mod.STATUS_OK

    def test_flipped_byte_refuses(self, tmp_path):
        path, _ = _checkpoint_after_anchor(tmp_path, count=3)
        data = bytearray(open(path, "rb").read())
        data[len(data) // 2] ^= 0xFF
        probe = str(tmp_path / "flip.kcfc")
        with open(probe, "wb") as f:
            f.write(bytes(data))
        ckpt, status = load_checkpoint(probe)
        assert ckpt is None and status != ckpt_mod.STATUS_OK

    def test_digest_stable_across_hash_seeds(self, tmp_path):
        """PYTHONHASHSEED must not reach the bytes: two subprocesses with
        different seeds serialize the same logical checkpoint to the same
        sha256 — the cross-process guarantee adoption's never-trust digest
        verify rests on."""
        script = r"""
import hashlib, sys
import numpy as np
from karpenter_core_tpu.fleet.checkpoint import checkpoint_bytes, enc
header = {"t": "header", "format": 1, "tenant": "acme", "version": 3,
          "state": {"version": 3, "planes": {"b": "2", "a": "1"}}}
tensors = {"t": "tensors",
           "assign": enc(np.arange(24, dtype=np.int32).reshape(4, 6)),
           "members_rows": [[0, ["u#0", "u#1"]], [1, ["v#0"]]],
           "pod_loc": {"u#0": [0, "new", 0], "v#0": [1, "new", 0]}}
blob = checkpoint_bytes(header, b"anchor-bytes", tensors)
print(hashlib.sha256(blob).hexdigest())
"""
        digests = set()
        for seed in ("0", "12345"):
            env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
            out = subprocess.run(
                [sys.executable, "-c", script], env=env,
                capture_output=True, text=True, timeout=120,
            )
            assert out.returncode == 0, out.stderr
            digests.add(out.stdout.strip())
        assert len(digests) == 1, f"hash-seed-dependent bytes: {digests}"


# -- restore ------------------------------------------------------------------


class TestRestore:
    def test_restored_next_solve_bit_identical(self, tmp_path):
        """The acceptance pin: drain replica r1, adopt the tenant on replica
        r2 from the checkpoint alone — the next delta solve is WARM and
        bit-identical to an uninterrupted server's answer."""
        provider = FakeCloudProvider()
        fleet1 = _fleet(tmp_path, rid="r1", ckpt_every=1)
        server1, client1 = _serve(provider, fleet=fleet1)
        r1 = _solve(client1, "acme", count=8)
        v1 = r1["tenant"]["sessionVersion"]
        r2 = _solve(client1, "acme", count=10, version=v1)
        assert r2["tenant"]["solveMode"] == "delta"
        server1.kc_service.drain(timeout_s=5.0)
        _stop(server1, client1)

        # the uninterrupted reference
        server_u, client_u = _serve(provider)
        u1 = _solve(client_u, "acme", count=8)
        u2 = _solve(client_u, "acme", count=10,
                    version=u1["tenant"]["sessionVersion"])
        u3 = _solve(client_u, "acme", count=12,
                    version=u2["tenant"]["sessionVersion"])
        _stop(server_u, client_u)

        from karpenter_core_tpu import fleet as fleet_mod

        warm_before = _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="warm"
        )
        fleet2 = _fleet(tmp_path, rid="r2", ckpt_every=1)
        server2, client2 = _serve(provider, fleet=fleet2)
        r3 = _solve(client2, "acme", count=12,
                    version=r2["tenant"]["sessionVersion"])
        assert r3["tenant"]["solveMode"] == "delta"
        assert r3["tenant"]["recovered"] == "warm"
        assert _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="warm"
        ) == warm_before + 1
        strip = lambda r: {k: v for k, v in r.items() if k != "tenant"}  # noqa: E731
        assert strip(r3) == strip(u3)
        _stop(server2, client2)

    def test_counted_lineage_checkpoints_the_materialized_rows(self, tmp_path):
        """The tenant path keeps a class's members as a count; its
        checkpoint still writes every member uid ``<base>#<j>``, sorted, as
        the lineage of copied members did — and the lineage restored from
        those rows (uid tuples) shrinks on its next delta tick exactly as
        the uninterrupted, counted one does."""
        provider = FakeCloudProvider()
        fleet1 = _fleet(tmp_path, rid="r1", ckpt_every=1)
        server1, client1 = _serve(provider, fleet=fleet1)
        r1 = _solve(client1, "acme", count=8)
        r2 = _solve(client1, "acme", count=10,
                    version=r1["tenant"]["sessionVersion"])
        assert r2["tenant"]["solveMode"] == "delta"
        ckpt, status = server1.kc_service._ckpt.load("acme")
        assert status == ckpt_mod.STATUS_OK
        (base,) = ckpt.header["uid_bases"]
        assert [[int(row), list(uids)]
                for row, uids in ckpt.tensors["members_rows"]] == [
            [0, sorted(f"{base}#{j}" for j in range(10))]]
        server1.kc_service.drain(timeout_s=5.0)
        _stop(server1, client1)

        server_u, client_u = _serve(provider)
        u1 = _solve(client_u, "acme", count=8)
        u2 = _solve(client_u, "acme", count=10,
                    version=u1["tenant"]["sessionVersion"])
        u3 = _solve(client_u, "acme", count=9,
                    version=u2["tenant"]["sessionVersion"])
        _stop(server_u, client_u)

        server2, client2 = _serve(
            provider, fleet=_fleet(tmp_path, rid="r2", ckpt_every=1))
        r3 = _solve(client2, "acme", count=9,
                    version=r2["tenant"]["sessionVersion"])
        assert r3["tenant"]["solveMode"] == "delta"
        assert r3["tenant"]["recovered"] == "warm"
        strip = lambda r: {k: v for k, v in r.items() if k != "tenant"}  # noqa: E731
        assert strip(r3) == strip(u3)
        assert r3["tenant"]["sessionVersion"] == u3["tenant"]["sessionVersion"]
        _stop(server2, client2)

    def test_stale_checkpoint_downgrades_to_replay(self, tmp_path):
        """A checkpoint older than the journal tail must NOT restore — the
        recovery rung demands full lineage-state equality and falls to
        chain replay, which still lands warm."""
        provider = FakeCloudProvider()
        # cadence 100: only the anchor checkpoints, deltas age it
        fleet = _fleet(tmp_path, rid="r1", ckpt_every=100)
        jdir = tmp_path / "fleet" / "journals" / "r1"
        server, client = _serve(provider, fleet=fleet, journal_dir=jdir)
        r1 = _solve(client, "acme", count=8)
        v = r1["tenant"]["sessionVersion"]
        for count in (10, 12, 14):
            r = _solve(client, "acme", count=count, version=v)
            assert r["tenant"]["solveMode"] == "delta"
        svc = server.kc_service
        ckpt, status = svc._ckpt.load("acme")
        assert status == ckpt_mod.STATUS_OK
        assert ckpt.state != svc.tenants.entries_snapshot()[
            "acme"].session.lineage_state(), "checkpoint must be stale here"
        import time

        time.sleep(0.3)  # the journal writer drains asynchronously
        _stop(server, client, abandon=True)  # SIGKILL shape: no final write

        server2, client2 = _serve(provider, fleet=_fleet(
            tmp_path, rid="r1", ckpt_every=100), journal_dir=jdir)
        r5 = _solve(client2, "acme", count=16, version=v)
        assert r5["tenant"]["solveMode"] == "delta"
        assert r5["tenant"]["recovered"] == "warm"
        _stop(server2, client2)

    def test_fresh_checkpoint_skips_replay_on_restart(self, tmp_path):
        """When the checkpoint IS as fresh as the journal tail, recovery
        restores from it in one deserialize — pinned by the replay-duration
        accounting staying warm while the checkpoint-restore path runs
        (the session must still answer delta, bit-identically)."""
        provider = FakeCloudProvider()
        fleet = _fleet(tmp_path, rid="r1", ckpt_every=1)
        jdir = tmp_path / "fleet" / "journals" / "r1"
        server, client = _serve(provider, fleet=fleet, journal_dir=jdir)
        r1 = _solve(client, "acme", count=8)
        v = r1["tenant"]["sessionVersion"]
        r2 = _solve(client, "acme", count=10, version=v)
        assert r2["tenant"]["solveMode"] == "delta"
        import time

        time.sleep(0.3)
        _stop(server, client, abandon=True)

        server2, client2 = _serve(provider, fleet=_fleet(
            tmp_path, rid="r1", ckpt_every=1), journal_dir=jdir)
        r3 = _solve(client2, "acme", count=12, version=v)
        assert r3["tenant"]["solveMode"] == "delta"
        assert r3["tenant"]["recovered"] == "warm"
        _stop(server2, client2)

    def test_peer_journal_replay_rung(self, tmp_path):
        """Checkpoint destroyed, peer journal intact: the adopting replica
        rebuilds the lineage by replaying the dead peer's chain (outcome
        ``replay``), and the delta still resumes warm."""
        provider = FakeCloudProvider()
        fleet1 = _fleet(tmp_path, rid="r1", ckpt_every=1)
        jdir1 = tmp_path / "fleet" / "journals" / "r1"
        server1, client1 = _serve(provider, fleet=fleet1, journal_dir=jdir1)
        r1 = _solve(client1, "acme", count=8)
        v1 = r1["tenant"]["sessionVersion"]
        r2 = _solve(client1, "acme", count=10, version=v1)
        import time

        time.sleep(0.3)
        _stop(server1, client1, abandon=True)
        # the checkpoints are gone (corrupt volume, races, ...): EVERY
        # retained generation, or the load ladder restores from an older one
        ckpt_dir = str(tmp_path / "fleet" / "checkpoints")
        for name in os.listdir(ckpt_dir):
            os.remove(os.path.join(ckpt_dir, name))

        from karpenter_core_tpu import fleet as fleet_mod

        replay_before = _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="replay"
        )
        fleet2 = _fleet(tmp_path, rid="r2", ckpt_every=1)
        server2, client2 = _serve(
            provider, fleet=fleet2,
            journal_dir=tmp_path / "fleet" / "journals" / "r2",
        )
        r3 = _solve(client2, "acme", count=12,
                    version=r2["tenant"]["sessionVersion"])
        assert r3["tenant"]["solveMode"] == "delta"
        assert r3["tenant"]["recovered"] == "warm"
        assert _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="replay"
        ) == replay_before + 1
        _stop(server2, client2)

    def test_prep_with_other_fields_refuses_and_falls_to_replay(self, tmp_path):
        """A checkpoint whose SolvePrep carries a field this reader's does
        not (a writer one schema apart, mid rolling upgrade) must refuse as
        FleetRestoreError rather than decode positionally into a misaligned
        prep, and the adopting replica must fall to the journal rung."""
        from karpenter_core_tpu import fleet as fleet_mod
        from karpenter_core_tpu.solver.incremental import (
            IncrementalSolveSession,
        )

        provider = FakeCloudProvider()
        fleet1 = _fleet(tmp_path, rid="r1", ckpt_every=1)
        jdir1 = tmp_path / "fleet" / "journals" / "r1"
        server1, client1 = _serve(provider, fleet=fleet1, journal_dir=jdir1)
        r1 = _solve(client1, "acme", count=8)
        r2 = _solve(client1, "acme", count=10,
                    version=r1["tenant"]["sessionVersion"])
        assert r2["tenant"]["solveMode"] == "delta"
        path = server1.kc_service._ckpt.path_for("acme")
        import time

        time.sleep(0.3)  # the journal writer drains asynchronously
        _stop(server1, client1, abandon=True)

        ckpt, status = load_checkpoint(path)
        assert status == ckpt_mod.STATUS_OK
        assert ckpt.tensors["prep"]["c"] == "SolvePrep"
        ckpt.tensors["prep"]["f"].append(None)  # one trailing field more
        with open(path, "wb") as f:
            f.write(ckpt_mod.checkpoint_bytes(
                ckpt.header, ckpt.anchor, ckpt.tensors))
        ckpt, status = load_checkpoint(path)
        assert status == ckpt_mod.STATUS_OK  # the frames and digest verify
        with pytest.raises(FleetRestoreError):
            ckpt_mod.restore_session(
                ckpt, IncrementalSolveSession(), provider)

        warm_before = _counter_value(fleet_mod.FAILOVER_TOTAL, outcome="warm")
        replay_before = _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="replay")
        server2, client2 = _serve(
            provider, fleet=_fleet(tmp_path, rid="r2", ckpt_every=1),
            journal_dir=tmp_path / "fleet" / "journals" / "r2",
        )
        r3 = _solve(client2, "acme", count=12,
                    version=r2["tenant"]["sessionVersion"])
        assert r3["tenant"]["solveMode"] == "delta"
        assert r3["tenant"]["recovered"] == "warm"
        assert _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="warm") == warm_before
        assert _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="replay") == replay_before + 1
        _stop(server2, client2)

    def test_no_artifact_reanchors(self, tmp_path):
        """Nothing to adopt from: the ladder bottoms out at the existing
        session-lost full solve (outcome ``reanchor``) — never an error."""
        from karpenter_core_tpu import fleet as fleet_mod

        reanchor_before = _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="reanchor"
        )
        fleet = _fleet(tmp_path, rid="r2")
        server, client = _serve(FakeCloudProvider(), fleet=fleet)
        r = _solve(client, "ghost", count=4, version=7)
        assert r["tenant"]["solveMode"] == "full"
        assert r["tenant"]["reason"] == "session-lost"
        assert _counter_value(
            fleet_mod.FAILOVER_TOTAL, outcome="reanchor"
        ) == reanchor_before + 1
        _stop(server, client)


# -- retention (PR 18: KC_FLEET_CHECKPOINT_KEEP) ------------------------------


class TestRetention:
    def _dir(self, tmp_path):
        return str(tmp_path / "fleet" / "checkpoints")

    def test_generations_bounded_and_newest_wins(self, tmp_path):
        """ckpt_every=1 and many delta solves: the shared directory holds at
        most ``keep`` generations per tenant, path_for points at the newest,
        and the newest generation is what load returns."""
        fleet = _fleet(tmp_path, ckpt_every=1)
        server, client = _serve(FakeCloudProvider(), fleet=fleet)
        try:
            r = _solve(client, "acme", count=4)
            v = r["tenant"]["sessionVersion"]
            for count in (6, 8, 10, 12):
                r = _solve(client, "acme", count=count, version=v)
            svc = server.kc_service
            plane = svc._ckpt
            names = sorted(os.listdir(self._dir(tmp_path)))
            assert len(names) == plane.keep, names
            assert all(".g" in n and n.endswith(".kcfc") for n in names)
            assert plane.path_for("acme") == os.path.join(
                self._dir(tmp_path), names[-1]
            )
            ckpt, status = plane.load("acme")
            assert status == ckpt_mod.STATUS_OK
            assert ckpt.path == plane.path_for("acme")
            live = svc.tenants.entries_snapshot()["acme"]
            assert ckpt.state == live.session.lineage_state()
        finally:
            _stop(server, client)

    def test_corrupt_newest_falls_back_to_previous_generation(self, tmp_path):
        """The durability win retention buys: flip a byte in the newest
        generation and load serves the previous COMPLETE generation instead
        of failing to the journal rung."""
        fleet = _fleet(tmp_path, ckpt_every=1)
        server, client = _serve(FakeCloudProvider(), fleet=fleet)
        try:
            r = _solve(client, "acme", count=4)
            _solve(client, "acme", count=6,
                   version=r["tenant"]["sessionVersion"])
            plane = server.kc_service._ckpt
            newest = plane.path_for("acme")
            prev_ckpt, prev_status = load_checkpoint(sorted(
                os.path.join(self._dir(tmp_path), n)
                for n in os.listdir(self._dir(tmp_path))
            )[0])
            assert prev_status == ckpt_mod.STATUS_OK
            data = bytearray(open(newest, "rb").read())
            data[len(data) // 2] ^= 0xFF
            with open(newest, "wb") as f:
                f.write(bytes(data))
            ckpt, status = plane.load("acme")
            assert status == ckpt_mod.STATUS_OK
            assert ckpt.path == prev_ckpt.path
            assert ckpt.version == prev_ckpt.version
        finally:
            _stop(server, client)

    def test_keep_env_override_and_floor(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KC_FLEET_CHECKPOINT_KEEP", "1")
        assert ckpt_mod.CheckpointPlane(str(tmp_path)).keep == 1
        monkeypatch.setenv("KC_FLEET_CHECKPOINT_KEEP", "5")
        assert ckpt_mod.CheckpointPlane(str(tmp_path)).keep == 5
        monkeypatch.setenv("KC_FLEET_CHECKPOINT_KEEP", "0")
        assert ckpt_mod.CheckpointPlane(str(tmp_path)).keep == 1
        monkeypatch.setenv("KC_FLEET_CHECKPOINT_KEEP", "bogus")
        assert ckpt_mod.CheckpointPlane(str(tmp_path)).keep == 2
        monkeypatch.delenv("KC_FLEET_CHECKPOINT_KEEP")
        assert ckpt_mod.CheckpointPlane(str(tmp_path)).keep == 2
        assert ckpt_mod.CheckpointPlane(str(tmp_path), keep=7).keep == 7

    def test_legacy_unsuffixed_file_is_generation_zero(self, tmp_path):
        """Upgrade path: a pre-retention ``<stem>-<digest>.kcfc`` file loads
        as generation 0, newer writes supersede it, and the sweep removes it
        once ``keep`` suffixed generations exist."""
        plane = ckpt_mod.CheckpointPlane(str(tmp_path), keep=1)
        legacy = os.path.join(str(tmp_path), ckpt_mod._safe_name("acme"))
        with open(legacy, "wb") as f:
            f.write(b"stale bytes from an old writer")
        assert plane.path_for("acme") == legacy
        gens = plane._generations("acme")
        assert gens == [(0, legacy)]
        # drop removes every generation including the legacy file
        plane.drop("acme")
        assert plane._generations("acme") == []
        assert not os.path.exists(legacy)
