"""Supervisor unit tests over a fake launcher: convergence, the
deregister -> drain -> wait retirement order, and dead-replica
replacement — no processes, no sockets (the subprocess path is
bin/smoke-autoscale.sh's)."""

import threading
import time
from typing import List

from keystone_tpu.autoscale.supervisor import Supervisor


class FakeHandle:
    def __init__(self, index):
        self.index = index
        self.name = f"replica-{index}"
        self.pid = 1000 + index
        self.log_path = None
        self.url = f"http://127.0.0.1:{9000 + index}"
        self._alive = True
        self.calls: List[str] = []

    def wait_listening(self, timeout_s):
        self.calls.append("wait_listening")
        return self.url

    def alive(self):
        return self._alive

    def drain(self):
        self.calls.append("drain")
        self._alive = False

    def kill(self):
        self.calls.append("kill")
        self._alive = False

    def wait(self, timeout_s):
        self.calls.append("wait")
        return True

    def status(self):
        return {"name": self.name, "url": self.url, "alive": self._alive}


class FakeLauncher:
    self_registering = True  # keep HTTP out of the unit tests

    def __init__(self):
        self.launched: List[FakeHandle] = []

    def launch(self, index):
        handle = FakeHandle(index)
        self.launched.append(handle)
        return handle


class RecordingSupervisor(Supervisor):
    """Records deregistration calls instead of dialing a router."""

    def __init__(self, launcher, **kw):
        super().__init__(launcher, "http://router:1", **kw)
        self.deregistered: List[str] = []
        self._launcher_ref = launcher

    def _deregister(self, url):
        # intercept the HTTP half; the ordering stays observable on
        # the handle's call log
        if url:
            self.deregistered.append(url)
            for h in self._launcher_ref.launched:
                if h.url == url:
                    h.calls.append("deregister")


def wait_until(pred, timeout_s=5.0):
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def make(launcher=None, **kw):
    launcher = launcher or FakeLauncher()
    return launcher, RecordingSupervisor(launcher, **kw)


def test_scale_to_grows_and_shrinks():
    launcher, sup = make()
    sup.scale_to(3)
    assert sup.target == 3
    # concurrent launches: membership is exact, append ORDER is not
    assert sorted(h.index for h in sup.replicas()) == [0, 1, 2]
    sup.scale_to(1)
    assert sup.target == 1
    assert len(sup.replicas()) == 1
    # the retired replicas drained on background threads
    survivors = set(sup.replicas())
    retired = [h for h in launcher.launched if h not in survivors]
    assert len(retired) == 2
    assert wait_until(
        lambda: all("drain" in h.calls for h in retired)
    )


def test_retirement_order_is_deregister_then_drain():
    """No new forwards may land on a draining replica: the roster
    removal must happen BEFORE the drain starts."""
    launcher, sup = make()
    sup.scale_to(2)
    sup.scale_to(1)
    survivors = set(sup.replicas())
    retired = next(
        h for h in launcher.launched if h not in survivors
    )
    assert wait_until(lambda: "drain" in retired.calls)
    assert retired.calls.index("deregister") < retired.calls.index("drain")
    assert sup.deregistered == [retired.url]


def test_reap_replaces_dead_replicas_and_counts():
    launcher, sup = make()
    sup.scale_to(2)
    launcher.launched[0]._alive = False  # kill -9
    assert sup.reap() == 1
    assert sup.replaced_total == 1
    # the dead one is gone from the roster, a replacement launched,
    # and the stale URL was deregistered
    assert len(sup.replicas()) == 2
    assert launcher.launched[0] not in sup.replicas()
    assert launcher.launched[0].url in sup.deregistered
    assert len(launcher.launched) == 3


def test_reap_without_deaths_is_a_noop():
    launcher, sup = make()
    sup.scale_to(2)
    assert sup.reap() == 0
    assert len(launcher.launched) == 2


def test_stop_retires_everything_and_refuses_further_work():
    launcher, sup = make()
    sup.scale_to(2)
    sup.stop()
    assert sup.target == 0
    assert sup.replicas() == []
    assert all("drain" in h.calls for h in launcher.launched)
    sup.scale_to(3)  # must be refused, not half-honored
    assert sup.replicas() == []
    assert len(launcher.launched) == 2
    assert sup.reap() == 0


def test_reap_counts_only_replacements_that_came_up():
    """A death whose replacement failed to start is NOT healed: the
    replaced count (and the exported counter fed from it) must say
    so, while the death itself stays visible as its event."""

    class DiesThenFails(FakeLauncher):
        def launch(self, index):
            handle = super().launch(index)
            if index > 0:  # every replacement fails the handshake
                handle.wait_listening = lambda timeout_s: None
            return handle

    events = []
    launcher = DiesThenFails()
    sup = RecordingSupervisor(
        launcher, startup_timeout_s=0.1, on_event=events.append
    )
    sup.scale_to(1)
    launcher.launched[0]._alive = False
    assert sup.reap() == 0
    assert sup.replaced_total == 0
    names = [e["event"] for e in events]
    assert "replica_died" in names
    replaced_ev = next(
        e for e in events if e["event"] == "replicas_replaced"
    )
    assert replaced_ev == {
        "event": "replicas_replaced", "died": 1, "replaced": 0,
    }


def test_failed_launch_is_killed_and_not_rostered():
    class NeverBinds(FakeLauncher):
        def launch(self, index):
            handle = super().launch(index)
            handle.wait_listening = lambda timeout_s: None
            return handle

    launcher, sup = make(NeverBinds(), startup_timeout_s=0.1)
    sup.scale_to(1)
    assert sup.replicas() == []
    assert "kill" in launcher.launched[0].calls


def test_events_emitted_for_lifecycle():
    events = []
    launcher = FakeLauncher()
    sup = RecordingSupervisor(launcher, on_event=events.append)
    sup.scale_to(1)
    launcher.launched[0]._alive = False
    sup.reap()
    names = [e["event"] for e in events]
    assert "replica_started" in names
    assert "replica_died" in names
    assert "replicas_replaced" in names


def test_status_snapshot():
    launcher, sup = make()
    sup.scale_to(2)
    doc = sup.status()
    assert doc["target"] == 2 and doc["running"] == 2
    assert len(doc["replicas"]) == 2


def test_concurrent_scale_and_reap_hold_the_target():
    """The control loop's reap and a scale_to racing must never
    overshoot the target or lose a handle."""
    launcher, sup = make()
    sup.scale_to(2)

    def churn():
        for _ in range(20):
            launcher.launched[-1]._alive = False
            sup.reap()

    t = threading.Thread(target=churn)
    t.start()
    for _ in range(10):
        sup.scale_to(2)
    t.join()
    sup.reap()
    live = [h for h in sup.replicas() if h.alive()]
    assert len(sup.replicas()) == 2, sup.status()
    assert len(live) == 2


def test_failed_start_event_carries_the_replicas_last_words(tmp_path):
    """A replica that dies before its handshake — e.g. serve-gateway
    refusing to start because the chip on this host is already held —
    must not fail silently: the supervisor's event quotes the child's
    last log line."""
    import subprocess
    import sys

    from keystone_tpu.autoscale.supervisor import SubprocessReplica

    message = "RuntimeError: the TPU on this host is already held by pid 1"

    class DyingLauncher:
        self_registering = True

        def launch(self, index):
            proc = subprocess.Popen(
                [sys.executable, "-c",
                 f"print({message!r}); raise SystemExit(1)"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
            return SubprocessReplica(
                proc, f"replica-{index}", str(tmp_path / f"r{index}.log")
            )

    events = []
    sup = Supervisor(
        DyingLauncher(), None, startup_timeout_s=30.0,
        on_event=events.append,
    )
    sup.scale_to(1)
    failed = [e for e in events if e["event"] == "replica_failed_to_start"]
    assert failed and failed[0]["reason"] == message
    assert sup.replicas() == []
