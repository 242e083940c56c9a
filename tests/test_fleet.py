"""Replica-fleet supervisor tests (``dlbb_tpu/serve/fleet.py``).

The supervisor's routing, fencing, hedging and degradation logic is
pure host-side state over feeds/controls, so most of this file unit-
tests a :class:`FleetSupervisor` constructed directly (``__init__``
spawns no threads and builds no engines — the meshes are only counted
until ``serve()`` runs).  The ``fleet_smoke``-marked tail runs the real
2-replica fleet on the simulated 8-rank mesh: a replica kill mid-trace
must fail its residents over and still reproduce the single-engine
oracle's completed tokens exactly, and the artifact family
(``fleet_*.json`` + manifest + journal + metrics.prom) must carry the
fleet columns the reports aggregate.  ``scripts/run_static_analysis.sh``
invokes the marked subset standalone.
"""

import ast
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from dlbb_tpu.comm.mesh import fault_domain_record, partition_devices
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.resilience import inject
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.fleet import (DEGRADE_LEVELS, FleetConfig,
                                  FleetSupervisor, ReplicaControl,
                                  ReplicaKilled, RequestFeed, _StartGate,
                                  run_fleet, validate_fleet)
from dlbb_tpu.serve.traffic import Request, generate_trace

MODEL = ModelConfig.from_dict(dict(
    hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
    ffn_intermediate=128, dtype="float32", attention="full"))
SERVING = ServingConfig.from_dict(dict(
    max_batch=8, block_size=8, max_seq=64, queue_capacity=64,
    hbm_budget_gb=None))

SMOKE_CONFIG = {
    "experiment": {"name": "fleet_smoke"},
    "model": dict(hidden_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=4, ffn_intermediate=128, dtype="float32",
                  attention="full"),
    # per-replica plan: 2 replicas x (dp=2 x tp=2) on the 8 sim devices
    "parallelism": {"data_parallel": 2, "world_size": 2},
    "serving": dict(max_batch=8, block_size=8, max_seq=64,
                    queue_capacity=64, hbm_budget_gb=None),
    "fleet": {"replicas": 2},
}


class _Journal:
    """Captures journal lines like SweepJournal.event would."""

    def __init__(self):
        self.events = []

    def event(self, event, config=None, **extra):
        self.events.append({"event": event, "config": config, **extra})

    def of(self, kind):
        return [e for e in self.events if e["event"] == kind]


def _sup(replicas=2, journal=None, serving=SERVING, **fleet_kw):
    return FleetSupervisor(
        MODEL, serving, FleetConfig(replicas=replicas, **fleet_kw),
        meshes=[object()] * replicas, journal=journal)


def _req(rid, prompt=8, out=4, deadline=None, prefix_seed=None,
         prefix_len=None):
    return Request(rid=rid, arrival_s=0.0, prompt_len=prompt,
                   output_len=out, seed=100 + rid, deadline_s=deadline,
                   prefix_seed=prefix_seed, prefix_len=prefix_len)


# ---------------------------------------------------------------- feed


def test_request_feed_semantics():
    feed = RequestFeed()
    assert bool(feed)            # open-but-empty: more work may come
    assert len(feed) == 0
    assert feed[0].arrival_s > 1e11 and feed[0].rid == -1  # horizon
    a, b, c = _req(0), _req(1), _req(2)
    feed.push(a)
    feed.push(b)
    feed.push_front(c)           # failover re-admission jumps the line
    assert [r.rid for r in feed] == [2, 0, 1]
    assert feed[0].rid == 2
    with pytest.raises(IndexError):
        feed[1]                  # feeds only expose the head
    assert feed.discard(0) and not feed.discard(99)
    assert feed.popleft().rid == 2
    feed.close()
    with pytest.raises(RuntimeError):
        feed.push(_req(3))
    with pytest.raises(RuntimeError):
        feed.push_front(_req(3))
    assert feed.popleft().rid == 1
    assert not feed              # drained AND closed -> loop exits
    with pytest.raises(IndexError):
        feed[0]


def test_replica_control_heartbeat_and_kill():
    ctl = ReplicaControl(0, _StartGate(0.05))
    assert ctl.beat_ema is None
    ctl.beat()
    ctl.beat()
    assert ctl.started and ctl.beats == 2 and ctl.beat_ema is not None
    ctl.check()                  # no kill flag, no active plan: no-op
    ctl.cancel(7, "hedge-lost")
    assert ctl.take_cancels() == [(7, "hedge-lost")]
    assert ctl.take_cancels() == []
    ctl.request_kill("replica-hung")
    ctl.request_kill("second-reason-ignored")
    assert ctl.kill_reason == "replica-hung"
    with pytest.raises(ReplicaKilled, match="replica-hung"):
        ctl.check()              # fenced replica can never dispatch again


# -------------------------------------------------------------- config


def test_fleet_config_roundtrip_and_unknown_key():
    cfg = FleetConfig.from_dict({"replicas": 3, "tick_s": 0.01})
    assert cfg.replicas == 3 and cfg.tick_s == 0.01
    assert FleetConfig.from_dict(cfg.to_dict()).to_dict() == cfg.to_dict()
    with pytest.raises(ValueError, match="max_replicas"):
        FleetConfig.from_dict({"max_replicas": 3})


@pytest.mark.parametrize("bad", [
    {"replicas": 0},
    {"heartbeat_factor": 0.5},
    {"heartbeat_min_s": 0.0},
    {"stall_timeout_s": -1.0},
    {"degrade_high_water": 0.0},
    {"tick_s": 0.0},
    {"hedge_min_completions": 0},
])
def test_fleet_config_validate_rejects(bad):
    with pytest.raises(ValueError):
        FleetConfig.from_dict(bad).validate()


def test_validate_fleet_admission_ladder():
    cfg = {"parallelism": {"data_parallel": 2, "world_size": 2}}
    assert validate_fleet(cfg, MODEL, SERVING, FleetConfig(2), 8) == (2, 2)
    # rung 1: fleet knobs
    with pytest.raises(ValueError, match="replicas"):
        validate_fleet(cfg, MODEL, SERVING, FleetConfig(0), 8)
    # non-(dp, tp) axes rejected before any partitioning
    with pytest.raises(ValueError, match="pipeline_parallel"):
        validate_fleet({"parallelism": {"pipeline_parallel": 2}},
                       MODEL, SERVING, FleetConfig(2), 8)
    # rung 2: lopsided fleet
    with pytest.raises(ValueError, match="equal failure domains"):
        validate_fleet(cfg, MODEL, SERVING, FleetConfig(3), 8)
    # rung 3: per-replica plan outgrows its domain
    with pytest.raises(ValueError, match="failure"):
        validate_fleet({"parallelism": {"data_parallel": 2,
                                        "world_size": 4}},
                       MODEL, SERVING, FleetConfig(2), 8)
    # rung 4: per-replica serving envelope (each replica carries its
    # OWN full KV planes, so the HBM budget is checked per domain)
    tight = ServingConfig.from_dict(dict(
        max_batch=8, block_size=8, max_seq=64, queue_capacity=64,
        hbm_budget_gb=1e-9))
    with pytest.raises(ValueError):
        validate_fleet(cfg, MODEL, tight, FleetConfig(2), 8)


def test_partition_devices_and_fault_domains():
    devs = [SimpleNamespace(id=i) for i in range(8)]
    groups = partition_devices(devs, 2)
    assert [[d.id for d in g] for g in groups] == [[0, 1, 2, 3],
                                                   [4, 5, 6, 7]]
    with pytest.raises(ValueError, match="partition"):
        partition_devices(devs, 3)
    with pytest.raises(ValueError):
        partition_devices(devs, 0)
    rec = fault_domain_record(groups)
    assert rec == {"0": [0, 1, 2, 3], "1": [4, 5, 6, 7]}
    assert json.loads(json.dumps(rec)) == rec  # manifest-serialisable


# ------------------------------------------------------------- routing


@pytest.mark.fleet_smoke
def test_routing_deterministic_least_loaded():
    reqs = [_req(i) for i in range(6)]

    def route_all():
        sup = _sup()
        for r in reqs:
            sup._route(r)
        return dict(sup._assign), list(sup._routed_count), sup

    a1, c1, sup = route_all()
    a2, c2, _ = route_all()
    assert a1 == a2 and c1 == c2  # same trace -> same routing table
    # equal-size requests alternate: least-loaded, ties to the lower id
    assert [a1[i] for i in range(6)] == [0, 1, 0, 1, 0, 1]
    assert c1 == [3, 3]
    assert [r.rid for r in sup.feeds[0]] == [0, 2, 4]
    assert sup._blocks[0] == sum(
        sup._blocks_for(r) for r in reqs if a1[r.rid] == 0)


@pytest.mark.fleet_smoke
def test_prefix_affinity_colocates_groups():
    sup = _sup()
    # two shared-prefix populations, interleaved arrivals
    reqs = [_req(i, prefix_seed=7 if i % 2 == 0 else 9, prefix_len=4)
            for i in range(8)]
    for r in reqs:
        sup._route(r)
    homes = {seed: {sup._assign[r.rid] for r in reqs
                    if r.prefix_seed == seed} for seed in (7, 9)}
    assert all(len(h) == 1 for h in homes.values())  # group -> ONE home
    # first member of each group misses (homes the prefix), rest hit
    assert sup._affinity_misses == 2
    assert sup._affinity_hits == 6
    # a plain trace never touches the affinity counters
    plain = _sup()
    for i in range(8):
        plain._route(_req(i))
    assert plain._affinity_hits == 0 and plain._affinity_misses == 0
    # fencing the home purges its affinity: the group re-homes on the
    # survivor instead of chasing a dead replica
    home = next(iter(homes[7]))
    sup._fence(home, "replica-killed")
    sup._route(_req(100, prefix_seed=7, prefix_len=4))
    assert sup._assign[100] != home
    assert sup._affinity[(7, 4)] == sup._assign[100]


def test_route_fails_closed_with_no_replicas():
    sup = _sup(replicas=1, journal=(j := _Journal()))
    sup._fence(0, "replica-crashed")
    sup._route(_req(0))
    assert sup._terminal[0] == "failed[no-replica]"
    assert j.of("request-failed")[0]["reason"] == "no-replica"


# ------------------------------------------------------------ failover


@pytest.mark.fleet_smoke
def test_failover_preserves_request_and_deadline():
    j = _Journal()
    sup = _sup(journal=j)
    reqs = [_req(i, deadline=2.5 + i) for i in range(4)]
    for r in reqs:
        sup._route(r)
    dead = [r for r in reqs if sup._assign[r.rid] == 0]
    survivors_before = [r.rid for r in sup.feeds[1]]
    sup._fence(0, "replica-killed", chain={"error": "ReplicaKilled: x"})

    assert sup._fenced[0] and sup._fence_reason[0] == "replica-killed"
    assert sup.feeds[0].closed
    assert sup.controls[0].kill_reason == "replica-killed"
    # residents moved to the survivor's feed HEAD, ahead of its own
    # queue (they already served their wait on the dead replica) — the
    # SAME Request objects, so arrival_s/deadline_s accounting is
    # untouched by the move
    moved = list(sup.feeds[1])[:len(dead)]
    assert {r.rid for r in moved} == {r.rid for r in dead}
    assert all(any(m is r for r in dead) for m in moved)
    assert [r.rid for r in sup.feeds[1]][len(dead):] == survivors_before
    assert all(sup._assign[r.rid] == 1 for r in dead)
    assert sup._failover_rids == {r.rid for r in dead}
    assert int(sup._failover_counter["replica-killed"]) == len(dead)
    # block estimates migrated, none leaked on the fenced side
    assert sup._blocks[0] == 0
    assert sup._blocks[1] == sum(sup._blocks_for(r) for r in reqs)
    # journal: fence + one failover line per moved request, with the
    # fence reason AND the original error chain on every line
    assert j.of("replica-fenced")[0]["reason"] == "replica-killed"
    fo = j.of("request-failover")
    assert {e["config"] for e in fo} == {f"request-{r.rid}" for r in dead}
    assert all(e["from_replica"] == 0 and e["to_replica"] == 1
               and e["reason"] == "replica-killed"
               and "error" in e for e in fo)
    # fencing is idempotent: a second fence must not re-route
    sup._fence(0, "replica-killed")
    assert len(sup._failover_log) == len(dead)


def test_failover_torn_rolls_back_and_retries():
    j = _Journal()
    sup = _sup(journal=j)
    reqs = [_req(i) for i in range(4)]
    for r in reqs:
        sup._route(r)
    with inject.plan_scope("serve-failover-torn:1"):
        sup._fence(0, "replica-killed")
    torn = j.of("failover-torn")
    assert len(torn) == 1 and torn[0]["attempt"] == 1
    # the retry committed exactly once: no double-routed request, no
    # leaked block estimate from the rolled-back attempt
    rids = [r.rid for r in sup.feeds[1]]
    assert sorted(rids) == [0, 1, 2, 3] and len(set(rids)) == 4
    assert sup._blocks[1] == sum(sup._blocks_for(r) for r in reqs)
    assert len(sup._failover_log) == 2
    assert len({e["rid"] for e in sup._failover_log}) == 2


def test_failover_orphans_fail_closed():
    # nowhere to fail over to: residents fail terminally, never hang
    j = _Journal()
    sup = _sup(replicas=1, journal=j)
    sup._route(_req(0, deadline=1.0))
    sup._fence(0, "replica-hung")
    assert sup._terminal[0] == "failed[replica-lost]"
    assert j.of("request-failed")[0]["reason"] == "replica-lost"
    assert len(sup._failover_log) == 0


# -------------------------------------------------------------- hedging


def test_hedge_resolution_first_completion_wins():
    sup = _sup()
    sup._route(_req(0, out=4))
    assert sup._assign[0] == 0
    sup._hedged[0] = 1
    # hedge copy (replica 1) completes first -> hedge WON, primary
    # copy cancelled
    sup._handle_event(1, 0, "request-completed",
                      {"latency_s": 0.2, "tokens": [5, 6, 7, 8]})
    assert sup._terminal[0] == "completed"
    assert sup._completed_by[0] == 1
    assert sup._tokens[0] == [5, 6, 7, 8]
    assert int(sup._hedge_counter["won"]) == 1
    assert sup.controls[0].take_cancels() == [(0, "hedge-lost")]
    # the loser's cancel arriving later must NOT overwrite the win
    sup._handle_event(0, 0, "request-canceled", {"reason": "hedge-lost"})
    assert sup._terminal[0] == "completed"
    # primary-wins mirror: loser is the hedge replica
    sup2 = _sup()
    sup2._route(_req(1))
    sup2._hedged[1] = 1
    sup2._handle_event(0, 1, "request-completed",
                       {"latency_s": 0.1, "tokens": [1]})
    assert int(sup2._hedge_counter["lost"]) == 1
    assert sup2.controls[1].take_cancels() == [(1, "hedge-lost")]


# ------------------------------------------------------------- ladder


@pytest.mark.fleet_smoke
def test_degrade_ladder_monotonic_and_journaled():
    j = _Journal()
    sup = _sup(journal=j)
    assert sup._level == 0 and DEGRADE_LEVELS[0] == "full"
    sup.degrade_to(2, "test overload")
    assert sup._level == 2
    # every level ENTERED is applied, journaled and counted — a jump
    # from 0 to 2 walks through 1
    assert [e["name"] for e in j.of("degrade-transition")] == [
        "no-speculation", "short-horizon"]
    assert [rec["level"] for rec in sup._degrade_log] == [1, 2]
    assert all(not c.spec_enabled for c in sup.controls)
    assert all(c.horizon_cap == 1 for c in sup.controls)
    assert int(sup._degrade_counter["no-speculation"]) == 1
    assert int(sup._degrade_counter["short-horizon"]) == 1
    # monotonic: the fleet never silently recovers a service class
    sup.degrade_to(1, "ignored")
    sup.degrade_to(2, "ignored")
    assert sup._level == 2 and len(sup._degrade_log) == 2
    with pytest.raises(ValueError, match="out of range"):
        sup.degrade_to(len(DEGRADE_LEVELS), "past the ladder")
    # level 3 sheds best-effort arrivals at the door, keeps SLO traffic
    sup.degrade_to(3, "capacity lost")
    sup._route(_req(50))                       # no deadline -> shed
    sup._route(_req(51, deadline=2.0))         # SLO class -> served
    assert sup._terminal[50] == "rejected[degraded-shed]"
    assert sup._shed == 1
    assert 51 in sup._assign and 51 not in sup._terminal
    shed = [e for e in j.of("request-rejected")
            if e["reason"] == "degraded-shed"]
    assert shed and shed[0]["config"] == "request-50"


# ------------------------------------------------- zero-injection pin


@pytest.mark.fleet_smoke
def test_fleet_is_host_side_only():
    """The PR-11 zero-injection pin, extended one level up: fleet.py
    must never build a device program AT ALL (no jax import, no
    jit/shard_map/pallas), so every ``inject.fire`` site it adds —
    replica kill/hang in ``ReplicaControl.check``, failover-torn in
    ``_fence`` — is host-side by construction and the jitted
    prefill/decode programs stay byte-identical with or without a
    fleet.  ``tests/test_serve_resilience.py`` pins the engine's device
    functions themselves."""
    import dlbb_tpu.serve.fleet as fleet_mod

    src = Path(fleet_mod.__file__).read_text()
    tree = ast.parse(src)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            assert not any(a.name == "jax" or a.name.startswith("jax.")
                           for a in node.names), \
                "fleet.py must stay host-side (imports jax)"
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            assert not (mod == "jax" or mod.startswith("jax.")), \
                f"fleet.py must stay host-side (from {mod} import ...)"
        name = (node.id if isinstance(node, ast.Name)
                else node.attr if isinstance(node, ast.Attribute) else None)
        assert name not in ("jit", "pjit", "shard_map", "pallas_call"), \
            f"device-program builder {name!r} found in fleet.py"


# ------------------------------------------------- engine integration


@pytest.fixture(scope="module")
def kill_run(tmp_path_factory, devices):
    """ONE oracle + ONE killed fleet run shared by the engine-backed
    smokes below (each fleet run compiles two replicas — sharing keeps
    the tier-1 budget honest)."""
    from dlbb_tpu.serve.bench import run_serving

    trace = generate_trace("poisson", 16, seed=5, rate=60.0,
                           prompt_range=(4, 12), output_range=(4, 8))
    out = tmp_path_factory.mktemp("fleet")
    single = {k: v for k, v in SMOKE_CONFIG.items() if k != "fleet"}
    oracle = run_serving(single, trace, verbose=False,
                         devices=devices[:4], journal=False,
                         capture_tokens=True)
    rep = run_fleet(SMOKE_CONFIG, trace, output_dir=str(out),
                    verbose=False, journal=True,
                    fault_plan="serve-replica-kill:@8",
                    capture_tokens=True)
    return oracle, rep, out


@pytest.mark.fleet_smoke
def test_fleet_smoke_kill_failover_token_identity(kill_run):
    """The headline contract: kill a replica mid-trace; every request
    still completes, failed-over requests re-prefill on the survivor,
    and the completed tokens are byte-identical to an unfaulted
    single-engine run (greedy decode depends only on (params seed,
    request), and every replica initialises from the same seed)."""
    oracle, rep, _ = kill_run

    fenced = [r for r in rep["replicas"]
              if r["fence_reason"] == "replica-killed"]
    assert len(fenced) == 1, rep["replicas"]
    outcomes = rep["requests"]["outcomes"]
    assert all(v == "completed" for v in outcomes.values()), outcomes
    assert rep["failovers"]["total"] >= 1
    assert all(r["reason"] == "replica-killed"
               for r in rep["failovers"]["requests"])
    assert rep["failover_ttft_penalty_s"] is not None
    assert rep["completed_tokens"] == oracle["completed_tokens"]
    # the survivor drained clean: nothing the failovers attached leaked
    ok = [r for r in rep["replicas"] if r["status"] == "ok"]
    assert ok and ok[0]["report"]["cache"]["blocks_reserved"] == 0


@pytest.mark.fleet_smoke
def test_fleet_smoke_artifact_family(kill_run):
    """The fleet run writes the full serving artifact family with the
    fleet markers the reports key on: fleet_<name>.json (schema
    dlbb_fleet_report_v1), a manifest with kind=fleet + fault_domains,
    the shared journal with per-replica tracks + the failover record,
    and metrics.prom with the failover/hedge/degrade counter
    families."""
    _, rep, out = kill_run
    assert rep["schema"] == "dlbb_fleet_report_v1"
    assert set(rep["fleet"]["fault_domains"]) == {"0", "1"}
    assert all(len(v) == 4 for v in rep["fleet"]["fault_domains"].values())
    assert rep["topology"]["fault_domains"] == rep["fleet"]["fault_domains"]

    art = json.loads((out / "fleet_fleet_smoke.json").read_text())
    assert art["schema"] == "dlbb_fleet_report_v1"
    manifest = json.loads((out / "serving_manifest.json").read_text())
    assert manifest["kind"] == "fleet"
    assert manifest["fault_domains"] == rep["fleet"]["fault_domains"]
    assert manifest["failovers"] == rep["failovers"]["total"] >= 1
    assert manifest["degrade_level"] == rep["degrade"]["level"]

    prom = (out / "metrics.prom").read_text()
    for family in ("serve_failovers_total", "serve_hedges_total",
                   "serve_degrade_transitions_total",
                   "serve_replica_resident_requests",
                   "serve_fleet_live_replicas"):
        assert family in prom, f"{family} missing from metrics.prom"
    assert 'serve_failovers_total{reason="replica-killed"}' in prom

    lines = [json.loads(ln) for ln in
             (out / "sweep_journal.jsonl").read_text().splitlines()]
    ups = [e for e in lines if e.get("event") == "replica-up"]
    assert {e["replica"] for e in ups} == {0, 1}
    fenced = [e for e in lines if e.get("event") == "replica-fenced"]
    assert fenced and fenced[0]["reason"] == "replica-killed"
    fo = [e for e in lines if e.get("event") == "request-failover"]
    assert len(fo) == rep["failovers"]["total"]
