"""Mechanics of the chaos harness itself: the ``chaos_hosts`` fixture, the
three fault actions, and the ParamStore bulletin board they act on.

These hosts are deliberately jax-free (plain numpy trees through the
exchange store) so the harness contract — faults fire inside the victim at
a deterministic round, exits carry the right codes, peers observe deaths
and departures — is pinned down fast, independent of any training loop.
"""
import signal

import numpy as np
import pytest

from repro.core.exchange import ParamStore, PeerTimeout
from repro.testing.chaos import ChaosInjector, Fault

pytestmark = pytest.mark.skipif(not hasattr(signal, "SIGKILL"),
                                reason="POSIX-only kill semantics")

# A minimal SSP-disciplined host: publish a recognizable tree each round,
# wait for peers under the staleness bound, read their freshest in-bound
# publication.  The injector is consulted at every round boundary, exactly
# where the stream wrapper would consult it in a real training loop.
_HOST = """
import json, os
import numpy as np

from repro.core.exchange import ParamStore
from repro.testing.chaos import ChaosInjector

HOST = int(os.environ["REPRO_HOST_ID"])
N = int(os.environ["REPRO_NUM_HOSTS"])
ROUNDS = int(os.environ["ROUNDS"])
S = int(os.environ.get("STALENESS", "0"))

store = ParamStore(os.environ["STORE_ROOT"], HOST, N, timeout=60.0)
injector = ChaosInjector.from_env(store=store)

reads = []
for r in range(ROUNDS):
    injector.step(r)
    store.publish(r, {"v": np.full(4, 10 * HOST + r, np.float32)})
    got = {}
    for p in store.peers():
        clock = store.wait_clock(p, r - S + 1)
        if clock <= r - S:
            continue  # departed and out of bound
        res = store.read_at_most(p, min(clock - 1, r),
                                 {"v": np.zeros(4, np.float32)})
        if res is None:
            continue  # peer has nothing old enough yet (early rounds, s>0)
        tree, tau = res
        assert tree["v"][0] == 10 * p + tau, (p, tau, tree)
        got[p] = tau
    reads.append(got)
print("RESULT::" + json.dumps({
    "host": HOST, "reads": reads,
    "delays": len(injector.injected),
    "clocks": store.clocks()}))
"""


def test_all_hosts_clean_without_faults(chaos_hosts, tmp_path):
    """Baseline: 3 independent hosts, lock-step (s=0), every read exact."""
    runs = chaos_hosts(_HOST, hosts=3, devices_per_host=1, global_mesh=False,
                       env={"ROUNDS": "4", "STORE_ROOT": str(tmp_path / "x")})
    for r in runs:
        res = r.result()
        peers = {str(p) for p in range(3) if p != r.host_id}
        # s=0 lock-step: every round reads every peer's *current* round
        assert res["reads"] == [{p: rd for p in peers} for rd in range(4)]
        assert res["clocks"] == {"0": 4, "1": 4, "2": 4}


def test_kill_fault_sigkills_victim_at_its_round(chaos_hosts, tmp_path):
    """A kill fault SIGKILLs exactly the targeted host at the targeted
    round; rounds before it completed, nothing after it ran."""
    runs = chaos_hosts(
        _HOST, hosts=2, devices_per_host=1, global_mesh=False, check=False,
        faults=[Fault(host=1, round=2, action="kill")],
        env={"ROUNDS": "4", "STALENESS": "2",
             "STORE_ROOT": str(tmp_path / "x")})
    survivor, victim = runs
    assert victim.killed, (victim.returncode, victim.stderr[-500:])
    assert "RESULT::" not in victim.stdout  # died mid-run, no final print
    # the victim published rounds 0 and 1, then died asking for round 2
    store = ParamStore(str(tmp_path / "x"), 0, 2)
    assert store.clock(1) == 2
    # the survivor (staleness 2 covers the gap) finished all 4 rounds; its
    # last round waits for the victim's round 1, so it reads that last
    # publication whatever the two processes' relative speed
    assert survivor.returncode == 0, survivor.stderr[-500:]
    res = survivor.result()
    assert res["reads"][-1] == {"1": 1}
    assert res["clocks"]["0"] == 4


def test_delay_fault_makes_a_straggler(chaos_hosts, tmp_path):
    """A delay fault sleeps inside the victim (recorded in .injected) and
    the cohort still completes — a straggler, not a death."""
    runs = chaos_hosts(
        _HOST, hosts=2, devices_per_host=1, global_mesh=False,
        faults=[Fault(host=0, round=1, action="delay", seconds=0.4)],
        env={"ROUNDS": "3", "STORE_ROOT": str(tmp_path / "x")})
    assert runs[0].result()["delays"] == 1
    assert runs[1].result()["delays"] == 0  # fault targeted host 0 only
    for r in runs:
        assert r.result()["clocks"] == {"0": 3, "1": 3}


def test_drop_fault_departs_gracefully(chaos_hosts, tmp_path):
    """A drop fault marks the host departed and exits DROP_EXIT_CODE; the
    peer stops waiting for it immediately (no timeout) and finishes."""
    runs = chaos_hosts(
        _HOST, hosts=2, devices_per_host=1, global_mesh=False, check=False,
        faults=[Fault(host=1, round=2, action="drop")],
        env={"ROUNDS": "4", "STORE_ROOT": str(tmp_path / "x")})
    survivor, dropped = runs
    assert dropped.dropped, (dropped.returncode, dropped.stderr[-500:])
    assert survivor.returncode == 0, survivor.stderr[-500:]
    store = ParamStore(str(tmp_path / "x"), 0, 2)
    assert store.has_left(1)
    assert 1 not in store.peers()
    # the survivor kept running after the departure: its clock reached 4
    assert survivor.result()["clocks"]["0"] == 4


def test_wait_clock_timeout_names_the_corpse(tmp_path):
    """A dead peer (never publishes) surfaces as PeerTimeout carrying WHO
    stalled the mesh — the signal an elastic controller resizes on."""
    store = ParamStore(str(tmp_path / "x"), 0, 2, timeout=0.2)
    store.publish(0, {"v": np.zeros(2, np.float32)})
    with pytest.raises(PeerTimeout) as ei:
        store.wait_clock(1, 1)
    assert ei.value.peer == 1
    assert ei.value.wanted_round == 0


def test_injector_inert_without_spec():
    """No REPRO_CHAOS in the environment -> injector does nothing, so
    programs can install it unconditionally."""
    inj = ChaosInjector.from_env(host_id=0)
    assert not inj
    for r in range(5):
        inj.step(r)  # must not raise, sleep, or kill
    assert inj.injected == []


def test_fault_validation():
    with pytest.raises(ValueError, match="unknown fault action"):
        Fault(host=0, round=1, action="explode")
    with pytest.raises(ValueError, match="seconds > 0"):
        Fault(host=0, round=1, action="delay")
    with pytest.raises(ValueError, match="two faults"):
        ChaosInjector([Fault(0, 1, "kill"), Fault(0, 1, "delay", 1.0)])


def test_wrap_stream_injects_by_stream_step():
    """The stream wrapper keys faults off the underlying stream position,
    proxying the runner-facing surface (step/seek/source/next)."""
    from repro.data.pipeline import BatchIterator

    def source(step):
        return {"data": np.full((4, 2), step, np.float32)}

    hits = []

    class Recorder(ChaosInjector):
        def step(self, round_index):
            hits.append(round_index)
            super().step(round_index)

    stream = Recorder([]).wrap_stream(BatchIterator(source))
    next(stream)
    next(stream)
    assert hits == [0, 1]
    assert stream.step == 2
    stream.seek(7)
    batch = next(stream)
    assert hits == [0, 1, 7]
    assert float(np.asarray(batch["data"])[0, 0]) == 7.0
    assert callable(stream.source)
