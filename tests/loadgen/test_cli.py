"""The serve-loadgen CLI: a requested fault that never fires must
turn the verdict red (a green verdict may never mean "the chaos
silently didn't happen"), and the happy path exits 0."""

import json

from keystone_tpu.loadgen import cli


def _verdict_from(out: str) -> dict:
    # the verdict is the last (indented) JSON document on stdout
    return json.loads(out[out.index('{\n "passed"'):])


def test_cli_red_when_requested_fault_never_fires(capsys):
    # match lane 99: the 1-lane gateway never routes there, so the
    # armed point can never fire — the run must NOT pass
    rc = cli.main([
        "--self-gateway", "--d", "8", "--buckets", "4,8",
        "--lanes", "1",
        "--synthetic", "30", "--rate", "100",
        "--fault", "gateway.lane.kill=lane:99",
        "--fault-at", "0.05", "--fault-for", "0.1",
        "--settle-s", "0.3", "--recovery-s", "1",
    ])
    assert rc == 1
    doc = _verdict_from(capsys.readouterr().out)
    assert doc["passed"] is False
    fired = [
        r for r in doc["invariants"]
        if r["name"] == "requested_fault_actually_fired"
    ]
    assert len(fired) == 1 and not fired[0]["passed"]
    assert doc["stats"]["injections"]["gateway.lane.kill"] == 0


def test_cli_green_fault_fires_and_verdict_reports_injections(capsys):
    # short run on a shared-CPU test host: the point here is the
    # injection-audit plumbing, so the p99 bound is deliberately
    # generous: a p99 taken here is a CPU timing (the recovery ratio
    # waits for the open-loop serving cell, ROADMAP R4)
    rc = cli.main([
        "--self-gateway", "--d", "8", "--buckets", "4,8",
        "--lanes", "2",
        "--synthetic", "160", "--rate", "80",
        "--fault", "gateway.lane.kill=lane:0",
        "--fault-at", "0.6", "--fault-for", "0.4",
        "--settle-s", "1.5", "--recovery-s", "8",
        "--p99-factor", "20",
    ])
    doc = _verdict_from(capsys.readouterr().out)
    assert rc == 0, doc
    assert doc["passed"] is True
    fired = [
        r for r in doc["invariants"]
        if r["name"] == "requested_fault_actually_fired"
    ]
    assert len(fired) == 1 and fired[0]["passed"]
    assert doc["stats"]["injections"]["gateway.lane.kill"] >= 1
