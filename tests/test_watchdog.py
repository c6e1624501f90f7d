"""Distributed-liveness unit tier: heartbeat staleness math, poison-key
convergence, hang-injection interruption, deadline-bounded KV-channel waits
with named missing keys — all tier-1-safe (no multi-process JAX; simulated
workers are Watchdog instances sharing an InMemoryKv, driven either
synchronously through tick(now) with a fake clock or on their real monitor
threads with sub-second deadlines)."""

import threading
import time

import numpy as np
import pytest

from paddlebox_tpu.config import LivenessConfig, flags
from paddlebox_tpu.parallel import host_plane
from paddlebox_tpu.parallel.watchdog import (
    DistributedStallError,
    InMemoryKv,
    PeerTracker,
    Watchdog,
    beat,
    current,
)
from paddlebox_tpu.utils import faults
from paddlebox_tpu.utils.faults import FaultPlan, FaultSpec
from paddlebox_tpu.utils.monitor import stats

pytestmark = pytest.mark.distributed

FAST = LivenessConfig(
    deadline_s=0.5, heartbeat_interval_s=0.1, poll_interval_s=0.05
)


def _sim_fleet(n, kv, conf=FAST, t0=100.0):
    """n simulated workers' watchdogs over one shared KV store, driven
    synchronously (install_current=False keeps them out of the process-wide
    registry so they can coexist)."""
    wds = [
        Watchdog(conf, rank=r, world=n, kv=kv, namespace="sim",
                 install_current=False)
        for r in range(n)
    ]
    for wd in wds:  # pin the staleness origin to the fake clock
        wd._tracker = PeerTracker()
        wd._tracker.observe(wd.rank, 0, "start", t0)
    return wds


# --------------------------------------------------------------------------- #
# staleness math
# --------------------------------------------------------------------------- #
def test_peer_tracker_staleness_math():
    tr = PeerTracker()
    tr.observe(1, 0, "feed", 10.0)
    assert tr.age(1, 12.0) == pytest.approx(2.0)
    # progress change resets the clock
    tr.observe(1, 5, "step", 12.0)
    assert tr.age(1, 12.5) == pytest.approx(0.5)
    # frozen progress does NOT reset it, even when heartbeats keep arriving
    tr.observe(1, 5, "step", 14.0)
    tr.observe(1, 5, "shuffle", 15.0)
    assert tr.age(1, 15.0) == pytest.approx(3.0)
    assert tr.last(1) == (5, "shuffle")  # stage label stays fresh
    assert tr.age(2, 15.0) is None  # never observed
    stale = tr.stale(15.5, deadline_s=3.0)
    assert stale == [(1, pytest.approx(3.5), 5, "shuffle")]
    assert tr.stale(15.5, deadline_s=10.0) == []


def test_staleness_is_observer_clocked_not_heartbeat_clocked():
    """The protocol must be clock-skew immune: a peer's heartbeat carries
    no timestamp the detector trusts — only progress counters, aged by the
    observer's own clock."""
    tr = PeerTracker()
    # the same progress observed repeatedly: age grows with OUR clock
    for t in (0.0, 1.0, 2.0, 3.0):
        tr.observe(7, 42, "step", t)
    assert tr.age(7, 3.0) == pytest.approx(3.0)


def test_local_stall_detection_and_error_fields():
    wd = Watchdog(FAST, rank=3, world=1, install_current=False)
    wd._tracker = PeerTracker()
    wd._tracker.observe(3, 0, "start", 0.0)
    wd.report("feed")
    assert not wd.tick(now=0.2)
    # frozen past the deadline
    assert wd.tick(now=1.0)
    err = wd.error
    assert isinstance(err, DistributedStallError)
    assert err.culprit == 3
    assert err.stage == "feed"
    assert err.kind == "local"
    assert err.age_s > FAST.deadline_s
    assert err.detected_by == 3
    assert "process 3" in str(err) and "'feed'" in str(err)
    with pytest.raises(DistributedStallError):
        wd.check()


def test_progress_keeps_watchdog_quiet():
    wd = Watchdog(FAST, rank=0, world=1, install_current=False)
    wd._tracker = PeerTracker()
    wd._tracker.observe(0, 0, "start", 0.0)
    for i in range(40):  # 4 simulated seconds, reporting every 0.1
        wd.report("step")
        assert not wd.tick(now=i * 0.1)
    assert not wd.aborted


# --------------------------------------------------------------------------- #
# poison-key convergence
# --------------------------------------------------------------------------- #
def test_poison_key_convergence_names_the_frozen_worker():
    kv = InMemoryKv()
    wds = _sim_fleet(3, kv)
    t0 = 100.0
    # everyone heartbeats and progresses except rank 1
    for step in range(4):
        t = t0 + step * 0.1
        for wd in wds:
            if wd.rank != 1:
                wd.report("step")
            assert not wd.tick(now=t)
    # push rank 1 past the deadline (healthy ranks keep reporting, so
    # only the frozen worker's progress counter is stale): every watchdog
    # must converge on culprit 1
    t = t0 + 0.65
    for wd in wds:
        if wd.rank != 1:
            wd.report("step")
        wd.tick(now=t)
    for wd in wds:
        assert wd.aborted
        assert wd.error.culprit == 1
        # the detector sees it as a peer stall; everyone else via poison
        assert wd.error.kind in ("peer", "poison")
    assert kv.get(wds[0].poison_key) is not None
    # convergence reconstructs the same structured story everywhere
    stages = {wd.error.stage for wd in wds}
    assert len(stages) == 1


def test_poison_payload_roundtrip_and_corruption():
    err = DistributedStallError(
        culprit=2, stage="hostplane:plan-4", kind="peer", age_s=12.5,
        progress=77, detected_by=0,
    )
    back = DistributedStallError.from_payload(err.to_payload(), reader_rank=1)
    assert back.culprit == 2
    assert back.stage == "hostplane:plan-4"
    assert back.kind == "poison"
    assert back.progress == 77
    # a corrupt payload still converges (culprit unknown)
    bad = DistributedStallError.from_payload("not json{", reader_rank=1)
    assert bad.kind == "poison" and bad.culprit == -1


# --------------------------------------------------------------------------- #
# deliberate membership shrink (PR 16: elastic fleet)
# --------------------------------------------------------------------------- #
def test_retired_rank_never_named_stall_culprit():
    """A drained-and-retired rank's frozen heartbeat is EXPECTED: after
    retire_peer, pushing its staleness arbitrarily past the deadline must
    not trip anyone's abort latch."""
    kv = InMemoryKv()
    wds = _sim_fleet(3, kv)
    t0 = 100.0
    for step in range(4):  # everyone healthy first
        t = t0 + step * 0.1
        for wd in wds:
            wd.report("step")
            assert not wd.tick(now=t)
    # rank 1 drains out of the fleet on purpose
    for wd in wds:
        if wd.rank != 1:
            wd.retire_peer(1)
    assert kv.get(wds[0]._hb_key(1)) is None  # heartbeat key pruned
    # rank 1 frozen forever; survivors keep working far past the deadline
    for step in range(30):
        t = t0 + 0.4 + step * 0.1
        for wd in wds:
            if wd.rank == 1:
                continue
            wd.report("step")
            assert not wd.tick(now=t)
    for wd in wds:
        if wd.rank != 1:
            assert not wd.aborted
    assert kv.get(wds[0].poison_key) is None


def test_poison_naming_retired_rank_is_ignored_and_cleared():
    """A racing detector that poisoned the fleet naming a rank that was
    deliberately retired (it saw the drain, not a stall): readers must
    drop the stale poison, clear the key, and NOT abort."""
    kv = InMemoryKv()
    wds = _sim_fleet(3, kv)
    wds[0].retire_peer(1)
    err = DistributedStallError(
        culprit=1, stage="step", kind="peer", age_s=9.9, progress=3,
        detected_by=2,
    )
    kv.set(wds[0].poison_key, err.to_payload())
    base = stats.get("watchdog.poison_retired_ignored")
    wds[0].report("step")
    assert not wds[0].tick(now=100.1)
    assert not wds[0].aborted
    assert kv.get(wds[0].poison_key) is None  # cleared for everyone
    assert stats.get("watchdog.poison_retired_ignored") == base + 1
    # a poison naming a NON-retired rank still aborts as before
    err2 = DistributedStallError(
        culprit=2, stage="step", kind="peer", age_s=9.9, progress=3,
        detected_by=0,
    )
    kv.set(wds[0].poison_key, err2.to_payload())
    assert wds[0].tick(now=100.2)
    assert wds[0].aborted and wds[0].error.culprit == 2


def test_retire_peer_is_idempotent_and_guards_own_rank():
    kv = InMemoryKv()
    wds = _sim_fleet(2, kv)
    tr = PeerTracker()
    tr.observe(1, 0, "step", 0.0)
    tr.deregister(1)
    assert tr.age(1, 5.0) is None
    tr.deregister(1)  # deregistering an unknown rank is a no-op
    wds[0].retire_peer(1)
    wds[0].retire_peer(1)  # idempotent
    assert wds[0]._is_retired(1)
    with pytest.raises(ValueError):
        wds[0].retire_peer(0)


def test_threaded_fleet_aborts_within_deadline():
    """Real monitor threads + heartbeats: freeze one of two workers and the
    whole simulated fleet aborts within ~2x the deadline, naming it."""
    kv = InMemoryKv()
    conf = LivenessConfig(
        deadline_s=0.4, heartbeat_interval_s=0.08, poll_interval_s=0.04
    )
    wd0 = Watchdog(conf, rank=0, world=2, kv=kv, namespace="thr",
                   install_current=False).start()
    wd1 = Watchdog(conf, rank=1, world=2, kv=kv, namespace="thr",
                   install_current=False).start()
    try:
        t0 = time.monotonic()
        # rank 0 keeps working; rank 1 never reports (frozen from birth)
        while not (wd0.aborted and wd1.aborted):
            wd0.report("step")
            if time.monotonic() - t0 > 2 * conf.deadline_s + 1.0:
                pytest.fail("fleet did not abort within 2x deadline")
            time.sleep(0.02)
        assert wd0.error.culprit == 1
        assert wd1.error.culprit == 1
    finally:
        wd0.close()
        wd1.close()


def test_heartbeat_fault_site():
    kv = InMemoryKv()
    wd = Watchdog(FAST, rank=0, world=2, kv=kv, namespace="hb",
                  install_current=False)
    wd._tracker = PeerTracker()
    wd._tracker.observe(0, 0, "start", 0.0)
    base = stats.get("watchdog.heartbeat_faults")
    with faults.fault_plan({"watchdog.heartbeat": "first:1"}):
        wd.tick(now=0.0)  # first publish attempt: injected failure
        assert kv.get(wd._hb_key(0)) is None
        assert stats.get("watchdog.heartbeat_faults") == base + 1
        wd.tick(now=0.2)  # past the heartbeat interval: publishes fine
        assert kv.get(wd._hb_key(0)) is not None


# --------------------------------------------------------------------------- #
# hang injection
# --------------------------------------------------------------------------- #
def test_hang_spec_parse():
    spec = FaultSpec.parse("hang:first:2")
    assert spec.hang and spec.fail_first == 2
    spec = FaultSpec.parse("hang:at:3,5")
    assert spec.hang and spec.at == (3, 5)
    with pytest.raises(ValueError):
        FaultSpec.parse("freeze:1")


def test_hang_interrupted_by_watchdog():
    conf = LivenessConfig(
        deadline_s=0.3, heartbeat_interval_s=0.05, poll_interval_s=0.03
    )
    wd = Watchdog(conf, rank=0, world=1).start()
    try:
        with faults.fault_plan({"train.step": "hang:first:1"}):
            t0 = time.monotonic()
            with pytest.raises(DistributedStallError) as ei:
                faults.inject("train.step")
            assert time.monotonic() - t0 < 2 * conf.deadline_s + 0.5
            assert ei.value.culprit == 0
        assert stats.get("faults.hung.train.step") >= 1
    finally:
        wd.close()
        faults.clear()


def test_hang_released_without_watchdog():
    with faults.fault_plan({"x.y": "hang:first:1"}):
        done = threading.Event()

        def run():
            faults.inject("x.y")  # hangs until released
            done.set()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert not done.wait(0.2)
        faults.release_hangs()
        assert done.wait(2.0)


def test_prefetcher_get_interrupted_by_abort():
    """A consumer blocked on a stalled producer's queue unblocks with the
    structured error within one poll slice."""
    from paddlebox_tpu.train.trainer import _FeedPrefetcher

    hold = threading.Event()

    def gen():
        hold.wait(10.0)  # the "stalled" producer
        yield "never"

    wd = Watchdog(FAST, rank=0, world=1).start()
    pf = _FeedPrefetcher(gen(), depth=1)
    try:
        wd.abort(
            DistributedStallError(
                culprit=0, stage="feed", kind="local", age_s=9.9,
                progress=0, detected_by=0,
            )
        )
        with pytest.raises(DistributedStallError):
            next(pf)
    finally:
        hold.set()
        wd.close()
        pf.close()


# --------------------------------------------------------------------------- #
# current-watchdog registry / beats
# --------------------------------------------------------------------------- #
def test_current_registry_and_beat():
    assert current() is None
    beat("feed")  # no-op without a watchdog
    wd = Watchdog(FAST, rank=0, world=1).start()
    try:
        assert current() is wd
        _, p0 = wd.state()
        beat("shuffle")
        stage, p1 = wd.state()
        assert stage == "shuffle" and p1 == p0 + 1
    finally:
        wd.close()
    assert current() is None


# --------------------------------------------------------------------------- #
# KvChannel: deadline-bounded waits, rich timeout, config resolution
# --------------------------------------------------------------------------- #
class _FakeCoordClient:
    """Coordination-service client double: blocking gets poll a dict and
    time out with the DEADLINE_EXCEEDED status string the real one uses."""

    def __init__(self):
        self.store = {}

    def key_value_set(self, k, v):
        self.store[k] = v

    def blocking_key_value_get(self, k, timeout_ms):
        end = time.monotonic() + timeout_ms / 1000.0
        while time.monotonic() < end:
            if k in self.store:
                return self.store[k]
            time.sleep(0.005)
        raise RuntimeError(f"DEADLINE_EXCEEDED: key {k}")

    def key_value_delete(self, k):
        self.store.pop(k, None)


@pytest.fixture
def fake_world(monkeypatch):
    """3-process world with a fake coordination client (rank 0's view)."""
    import jax

    client = _FakeCoordClient()
    monkeypatch.setattr(host_plane, "_client", lambda: client)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    monkeypatch.setattr(jax, "process_count", lambda: 3)
    return client


def _peer_payload(x: np.ndarray, codec: str = "varint") -> str:
    """What a same-version peer would post for ``x`` (codec-framed,
    base64'd — the KvChannel wire format)."""
    import base64

    return base64.b64encode(
        host_plane._encode_array(np.ascontiguousarray(x), codec)
    ).decode("ascii")


def test_kvchannel_timeout_names_missing_keys(fake_world):
    ch = host_plane.KvChannel("plan-7", timeout_s=0.4)
    ch.POLL_S = 0.05
    # peer 1 answers, peer 2 never does
    x = np.asarray([5], dtype=np.int64)
    fake_world.store["pbox_hp/plan-7/0/1"] = _peer_payload(
        np.asarray([6], np.int64), ch.codec
    )
    with pytest.raises(host_plane.HostPlaneTimeout) as ei:
        ch.allgather(x)
    err = ei.value
    assert err.channel == "plan-7" and err.seq == 0
    assert [r for r, _ in err.missing] == [2]
    assert "pbox_hp/plan-7/0/2" in str(err)
    assert "process(es) [2]" in str(err)


def test_kvchannel_completes_when_peers_answer(fake_world):
    ch = host_plane.KvChannel("plan-8", timeout_s=2.0)
    ch.POLL_S = 0.05
    for r in (1, 2):
        fake_world.store[f"pbox_hp/plan-8/0/{r}"] = _peer_payload(
            np.asarray([r], np.int64), ch.codec
        )
    out = ch.allgather(np.asarray([0], dtype=np.int64))
    np.testing.assert_array_equal(out, np.asarray([[0], [1], [2]]))
    ch.close()


def test_kvchannel_gather_bytes_varlen(fake_world):
    """Opaque varlen byte payloads gather in rank order with no padding
    contract (the census wire's transport face)."""
    import base64

    ch = host_plane.KvChannel("plan-b", timeout_s=2.0)
    ch.POLL_S = 0.05
    fake_world.store["pbox_hp/plan-b/0/1"] = base64.b64encode(
        b"peer-one-longer-payload"
    ).decode()
    fake_world.store["pbox_hp/plan-b/0/2"] = base64.b64encode(b"p2").decode()
    out = ch.gather_bytes(b"mine")
    assert out == [b"mine", b"peer-one-longer-payload", b"p2"]
    ch.close()


def test_kvchannel_codec_mismatch_fails_loudly(fake_world):
    """A legacy (unframed) peer payload on a codec-enabled channel raises
    the structured codec error naming the peer — never a silent
    frombuffer of garbage."""
    ch = host_plane.KvChannel("plan-m", timeout_s=2.0, codec="varint")
    ch.POLL_S = 0.05
    # peer 1 speaks the old bare-bytes wire; peer 2 is well-formed
    fake_world.store["pbox_hp/plan-m/0/1"] = (
        __import__("base64").b64encode(
            np.asarray([6], np.int64).tobytes()
        ).decode()
    )
    fake_world.store["pbox_hp/plan-m/0/2"] = _peer_payload(
        np.asarray([7], np.int64), "varint"
    )
    with pytest.raises(host_plane.HostPlaneCodecError) as ei:
        ch.allgather(np.asarray([0], dtype=np.int64))
    assert ei.value.rank == 1 and ei.value.channel == "plan-m"
    # and the mirror case: a framed payload hitting a legacy rank
    ch2 = host_plane.KvChannel("plan-m2", timeout_s=2.0, codec="legacy")
    ch2.POLL_S = 0.05
    fake_world.store["pbox_hp/plan-m2/0/1"] = _peer_payload(
        np.asarray([6], np.int64), "varint"
    )
    fake_world.store["pbox_hp/plan-m2/0/2"] = (
        __import__("base64").b64encode(
            np.asarray([7], np.int64).tobytes()
        ).decode()
    )
    with pytest.raises(host_plane.HostPlaneCodecError):
        ch2.allgather(np.asarray([0], dtype=np.int64))


def test_kvchannel_codec_roundtrip_all_modes(fake_world):
    """Every codec mode round-trips int and float payloads exactly."""
    for codec in ("varint", "raw", "legacy"):
        for x in (
            np.asarray([[5, -3, 4095, 4095]], dtype=np.int32),
            np.asarray([1.5, -2.25], dtype=np.float32),
            np.asarray([0, (1 << 63)], dtype=np.uint64),
        ):
            name = f"plan-c-{codec}-{x.dtype}"
            ch = host_plane.KvChannel(name, timeout_s=2.0, codec=codec)
            ch.POLL_S = 0.05
            for r in (1, 2):
                fake_world.store[f"pbox_hp/{name}/0/{r}"] = _peer_payload(
                    x + x.dtype.type(r), codec
                )
            out = ch.allgather(x)
            assert out.dtype == x.dtype
            np.testing.assert_array_equal(out[0], x)
            np.testing.assert_array_equal(out[2], x + x.dtype.type(2))
            ch.close()


def test_kvchannel_records_collective_digest(fake_world):
    """Every allgather leaves a (channel, seq, op) digest in the flight
    ring — the runtime witness pbox_doctor's cross-rank check consumes."""
    from paddlebox_tpu.telemetry import flight

    rec = flight.reset_for_tests()
    ch = host_plane.KvChannel("plan-w", timeout_s=2.0)
    ch.POLL_S = 0.05
    for s in range(2):
        for r in (1, 2):
            fake_world.store[f"pbox_hp/plan-w/{s}/{r}"] = _peer_payload(
                np.asarray([r], np.int64), ch.codec
            )
        ch.allgather(np.asarray([0], dtype=np.int64))
    digests = [
        r for r in rec.snapshot()
        if r["kind"] == "collective" and r.get("channel") == "plan-w"
    ]
    assert [(d["seq"], d["op"], d["rank"]) for d in digests] == [
        (0, "allgather", 0), (1, "allgather", 0),
    ]
    ch.close()
    flight.reset_for_tests()


def test_kvchannel_wait_interrupted_by_watchdog_abort(fake_world):
    wd = Watchdog(FAST, rank=0, world=1).start()
    ch = host_plane.KvChannel("plan-9", timeout_s=30.0)
    ch.POLL_S = 0.05
    try:
        wd.abort(
            DistributedStallError(
                culprit=2, stage="step", kind="peer", age_s=9.0,
                progress=4, detected_by=0,
            )
        )
        t0 = time.monotonic()
        with pytest.raises(DistributedStallError):
            ch.allgather(np.asarray([1], dtype=np.int64))
        assert time.monotonic() - t0 < 5.0  # nowhere near the 30s timeout
    finally:
        # the other peer's read is still polling in the channel's pool: let
        # the pending abort end it too, while the watchdog is still the
        # current one -- left behind it polls for 30 s and beats
        # "hostplane:plan-9" into whichever watchdog a later test of this
        # process starts (tests/test_postmortem.py then reads that stage)
        ch._pool.shutdown(wait=True)
        wd.close()


def test_kvchannel_default_timeout_resolution(fake_world, monkeypatch):
    # flags default
    assert host_plane.KvChannel("a").timeout_s == flags.hostplane_timeout_s
    # env flag override
    monkeypatch.setenv("PBOX_HOSTPLANE_TIMEOUT_S", "123.0")
    assert host_plane.KvChannel("b").timeout_s == 123.0
    # the active watchdog's LivenessConfig outranks the flag
    conf = LivenessConfig(
        deadline_s=5.0, heartbeat_interval_s=1.0, poll_interval_s=0.5,
        hostplane_timeout_s=42.0,
    )
    wd = Watchdog(conf, rank=0, world=1).start()
    try:
        assert host_plane.KvChannel("c").timeout_s == 42.0
    finally:
        wd.close()


def test_kvchannel_allgather_fault_site(fake_world):
    with faults.fault_plan({"hostplane.allgather": "first:1"}):
        ch = host_plane.KvChannel("plan-f", timeout_s=1.0)
        with pytest.raises(faults.FaultInjected):
            ch.allgather(np.asarray([1], dtype=np.int64))


# --------------------------------------------------------------------------- #
# LivenessConfig
# --------------------------------------------------------------------------- #
def test_liveness_config_validation():
    with pytest.raises(ValueError):
        LivenessConfig(deadline_s=0.0)
    with pytest.raises(ValueError):
        LivenessConfig(deadline_s=10.0, heartbeat_interval_s=10.0)
    with pytest.raises(ValueError):
        LivenessConfig(poll_interval_s=0.0)


def test_liveness_config_from_flags(monkeypatch):
    monkeypatch.setenv("PBOX_LIVENESS_DEADLINE_S", "77.0")
    monkeypatch.setenv("PBOX_LIVENESS_HEARTBEAT_S", "7.0")
    conf = LivenessConfig.from_flags()
    assert conf.deadline_s == 77.0
    assert conf.heartbeat_interval_s == 7.0


def test_for_trainer_disabled_and_single_process():
    from paddlebox_tpu.parallel import watchdog as wmod

    assert wmod.for_trainer(None, "x") is None
    conf = LivenessConfig(
        deadline_s=5.0, heartbeat_interval_s=1.0, poll_interval_s=0.5,
        enabled=False,
    )
    assert wmod.for_trainer(conf, "x") is None
    wd = wmod.for_trainer(FAST, "x")
    assert wd is not None and wd.kv is None and wd.world == 1
    # single-process watchdogs must never arm the hard-exit reaper
    assert wd._hard_exit_grace_s is None
