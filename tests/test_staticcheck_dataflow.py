"""Tier-1 tests for the flow-sensitive staticcheck layer.

Three strata, matching how the machinery is built:

- CFG structure (staticcheck/cfg.py): loop back-edges, try/finally
  cleanup on both normal and exceptional paths, async-with
  enter/exit markers, EXC edges observing pre-statement state, and
  catch-all handlers stopping the escape to the exceptional exit.
- the four CFG-backed rules (page-lifecycle, state-machine,
  lock-discipline, endpoint-contract): one planted-violation fixture
  and one clean shape each, plus the real tree staying clean per
  rule (the aggregate gate lives in test_staticcheck.py).
- the CLI satellites: --diff line filtering, SARIF rendering, and
  baseline prune/stale detection.

Plus runtime regressions for the drift the new rules surfaced:
Sequence.transition() guarding untabled moves, and the fake engine's
/version and /debug/steps mirrors of the real server surface.
"""

import ast
import asyncio
import json
import pathlib
import textwrap

from production_stack_tpu.staticcheck import (
    Finding,
    Project,
    run_rules,
)
from production_stack_tpu.staticcheck import baseline as baseline_mod
from production_stack_tpu.staticcheck import dataflow
from production_stack_tpu.staticcheck import diff as diff_mod
from production_stack_tpu.staticcheck import sarif as sarif_mod
from production_stack_tpu.staticcheck.cfg import (
    BACK,
    CFG,
    EXC,
    WithEnter,
    WithExit,
    contains_call,
    default_raises,
)

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _fn(src):
    """First function definition parsed from dedented ``src``."""
    tree = ast.parse(textwrap.dedent(src))
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node
    raise AssertionError("no function in fixture")


def _run(sources, rule):
    project = Project.from_sources(
        {path: textwrap.dedent(text) for path, text in sources.items()})
    return [f for f in run_rules(project, rules=[rule])
            if f.rule == rule]


# ---- CFG structure -----------------------------------------------------


def test_cfg_loop_has_one_back_edge_to_head():
    cfg = CFG(_fn("""\
        def f(n):
            total = 0
            while n > 0:
                total += n
                n -= 1
            return total
        """), raises=lambda _s, _t: False)
    back = cfg.back_edges()
    assert len(back) == 1
    _src, head = back[0]
    # The loop head carries the While statement itself so analyzers
    # can read its test.
    assert any(isinstance(el, ast.While) for el in head.elements)


def test_cfg_try_finally_cleanup_on_normal_and_exception_paths():
    # Lattice: {"held"} after acquire, cleared by release. The
    # finally must run on the fallthrough path AND on the path where
    # work() raises, so neither exit sees the lock held.
    # Only work() raises here — under default_raises the release()
    # call itself gets an EXC edge too (on which the lock is
    # legitimately still held), which is precision this test is not
    # about.
    def only_work_raises(stmt, _in_try):
        return any(isinstance(n, ast.Call)
                   and getattr(n.func, "id", "") == "work"
                   for n in ast.walk(stmt))

    cfg = CFG(_fn("""\
        def f(lock):
            lock.acquire()
            try:
                work()
            finally:
                lock.release()
            return 1
        """), raises=only_work_raises)

    def transfer(state, el, _kind):
        if not isinstance(el, ast.AST):
            return state
        for node in ast.walk(el):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute):
                if node.func.attr == "acquire":
                    return state | {"held"}
                if node.func.attr == "release":
                    return state - {"held"}
        return state

    exits = dataflow.facts_at_exit(cfg, frozenset(), transfer)
    assert exits["exit"] == frozenset()
    # work() raised -> exceptional copy of the finally still released.
    assert exits["raise_exit"] == frozenset()


def test_cfg_async_with_emits_enter_exit_markers_on_all_paths():
    cfg = CFG(_fn("""\
        async def f(self):
            async with self.lock:
                await work()
            return 1
        """), raises=default_raises)
    elements = [el for b in cfg.blocks for el in b.elements]
    enters = [el for el in elements if isinstance(el, WithEnter)]
    exits_ = [el for el in elements if isinstance(el, WithExit)]
    assert len(enters) == 1 and enters[0].is_async
    # One WithExit on the normal path, one cloned onto the
    # exceptional escape (await work() can raise).
    assert len(exits_) == 2

    def transfer(state, el, _kind):
        if isinstance(el, WithEnter):
            return state | {"held"}
        if isinstance(el, WithExit):
            return state - {"held"}
        return state

    exits = dataflow.facts_at_exit(cfg, frozenset(), transfer)
    assert exits["exit"] == frozenset()
    assert exits["raise_exit"] == frozenset()


def test_cfg_exc_edge_carries_pre_statement_state():
    # The allocation statement itself can raise; on that edge the
    # binding never happened, so only the normal exit holds the fact.
    cfg = CFG(_fn("""\
        def f(self):
            pages = self.cache.allocate_pages(1)
        """), raises=lambda s, _t: contains_call(s))

    def transfer(state, el, _kind):
        if (isinstance(el, ast.Assign)
                and isinstance(el.targets[0], ast.Name)):
            return state | {el.targets[0].id}
        return state

    exits = dataflow.facts_at_exit(cfg, frozenset(), transfer)
    assert exits["exit"] == frozenset({"pages"})
    assert exits["raise_exit"] == frozenset()


def test_cfg_catch_all_handler_stops_escape():
    # With `except Exception` the body's raise cannot reach the
    # exceptional exit; drop the handler and it must.
    caught = CFG(_fn("""\
        def f(self):
            try:
                raise ValueError("x")
            except Exception:
                return 0
        """), raises=default_raises)
    reachable = {b.id for b in caught.reachable()}
    assert caught.raise_exit.id not in reachable

    uncaught = CFG(_fn("""\
        def f(self):
            try:
                raise ValueError("x")
            except KeyError:
                return 0
        """), raises=default_raises)
    reachable = {b.id for b in uncaught.reachable()}
    assert uncaught.raise_exit.id in reachable


def test_cfg_break_and_continue_route_through_finally():
    # break inside try/finally inside a loop clones the finally onto
    # the exit path; the continue edge back to the head is BACK.
    cfg = CFG(_fn("""\
        def f(items, lock):
            for item in items:
                lock.acquire()
                try:
                    if item:
                        break
                    continue
                finally:
                    lock.release()
            return 1
        """), raises=lambda _s, _t: False)

    def transfer(state, el, _kind):
        # Loop heads carry the whole For statement (so analyzers can
        # read its iterable) — don't credit the head with effects
        # nested in the loop body.
        if not isinstance(el, ast.AST) or isinstance(
                el, (ast.For, ast.While)):
            return state
        for node in ast.walk(el):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute):
                if node.func.attr == "acquire":
                    return state | {"held"}
                if node.func.attr == "release":
                    return state - {"held"}
        return state

    exits = dataflow.facts_at_exit(cfg, frozenset(), transfer)
    assert exits["exit"] == frozenset()
    assert len(cfg.back_edges()) >= 1


# ---- page-lifecycle ----------------------------------------------------


def test_page_lifecycle_catches_alloc_leak_on_exception_path():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            def admit(self, seq):
                pages = list(self.cache.allocate_pages(4))
                self.queue.add_sequence(seq)
                seq.pages.extend(pages)
            """,
    }, "page-lifecycle")
    assert len(findings) == 1
    assert "KV pages allocated into 'pages'" in findings[0].message
    assert "exception path" in findings[0].message


def test_page_lifecycle_accepts_freed_on_failure_path():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            def admit(self, seq):
                pages = list(self.cache.allocate_pages(4))
                try:
                    self.queue.add_sequence(seq)
                except Exception:
                    self.cache.free_pages(pages)
                    raise
                seq.pages.extend(pages)
            """,
    }, "page-lifecycle")
    assert findings == []


def test_page_lifecycle_catches_stranded_awaiting_kv_park():
    findings = _run({
        "production_stack_tpu/engine/engine.py": """\
            def park(self, seq):
                seq.transition(SequenceState.AWAITING_KV)
                if not self.has_capacity:
                    return
                self.waiting_kv.append(seq)
            """,
    }, "page-lifecycle")
    assert len(findings) == 1
    assert "parked in AWAITING_KV" in findings[0].message


def test_page_lifecycle_accepts_park_with_sink_on_every_path():
    findings = _run({
        "production_stack_tpu/engine/engine.py": """\
            def park(self, seq):
                seq.transition(SequenceState.AWAITING_KV)
                if not self.has_capacity:
                    self.scheduler.abort_sequence(seq.seq_id)
                    return
                self.waiting_kv.append(seq)
            """,
    }, "page-lifecycle")
    assert findings == []


def test_page_lifecycle_waiver_suppresses():
    findings = _run({
        "production_stack_tpu/engine/engine.py": """\
            def park(self, seq):
                seq.transition(SequenceState.AWAITING_KV)  # lint: allow-page-lifecycle
                return
            """,
    }, "page-lifecycle")
    assert findings == []


# ---- state-machine -----------------------------------------------------

_SEQUENCE_FIXTURE = """\
    class SequenceState:
        WAITING = "waiting"
        RUNNING = "running"
        FINISHED = "finished"
        ABORTED = "aborted"

    SEQUENCE_TRANSITIONS = (
        ("new", "waiting", "arrival"),
        ("waiting", "running", "scheduled"),
        ("running", "finished", "done"),
    )

    class Sequence:
        def transition(self, new_state):
            self.state = new_state
    """

_DOCS_FIXTURE = """\
    <!-- sequence-states:begin -->
    | `new` | `waiting` | arrival |
    | `waiting` | `running` | scheduled |
    | `running` | `finished` | done |
    <!-- sequence-states:end -->
    """


def test_state_machine_catches_bypass_bad_ctor_and_untabled_dest():
    findings = _run({
        "production_stack_tpu/engine/sequence.py": _SEQUENCE_FIXTURE,
        "docs/sequence_states.md": _DOCS_FIXTURE,
        "production_stack_tpu/engine/scheduler.py": """\
            from production_stack_tpu.engine.sequence import (
                Sequence, SequenceState)

            def bad_write(seq):
                seq.state = SequenceState.RUNNING

            def bad_ctor():
                return Sequence(state=SequenceState.RUNNING)

            def bad_dest(seq):
                seq.transition(SequenceState.ABORTED)
            """,
    }, "state-machine")
    messages = "\n".join(f.message for f in findings)
    assert "direct .state write bypasses" in messages
    assert "no ('new', ...) row" in messages
    assert "never a destination" in messages
    assert len(findings) == 3


def test_state_machine_accepts_clean_usage_and_docs():
    findings = _run({
        "production_stack_tpu/engine/sequence.py": _SEQUENCE_FIXTURE,
        "docs/sequence_states.md": _DOCS_FIXTURE,
        "production_stack_tpu/engine/scheduler.py": """\
            from production_stack_tpu.engine.sequence import (
                Sequence, SequenceState)

            def ok(seq):
                seq.transition(SequenceState.RUNNING)
                return Sequence(state=SequenceState.WAITING)
            """,
    }, "state-machine")
    assert findings == []


def test_state_machine_keeps_docs_in_sync_both_directions():
    stale_docs = _DOCS_FIXTURE.replace(
        "| `running` | `finished` | done |",
        "| `running` | `aborted` | stale row |")
    findings = _run({
        "production_stack_tpu/engine/sequence.py": _SEQUENCE_FIXTURE,
        "docs/sequence_states.md": stale_docs,
    }, "state-machine")
    messages = "\n".join(f.message for f in findings)
    # Table row missing from the docs block...
    assert "but undocumented" in messages
    # ...and a documented row the table no longer has.
    assert "stale row or missing" in messages


# ---- lock-discipline ---------------------------------------------------


def test_lock_discipline_catches_await_under_sync_lock_and_bare_rmw():
    findings = _run({
        "production_stack_tpu/router/service.py": """\
            class Counter:
                async def bump(self):
                    with self._lock:
                        await self.flush()

                async def inc(self):
                    self.total += 1

                async def dec(self):
                    self.total -= 1
            """,
    }, "lock-discipline")
    messages = "\n".join(f.message for f in findings)
    assert "await in Counter.bump while" in messages
    assert "sync lock self._lock is held" in messages
    rmw = [f for f in findings
           if "self.total is read-modify-written" in f.message]
    assert len(rmw) == 2  # one per bare site


def test_lock_discipline_accepts_async_with_guarded_counters():
    findings = _run({
        "production_stack_tpu/router/service.py": """\
            class Counter:
                async def inc(self):
                    async with self._lock:
                        self.total += 1

                async def dec(self):
                    async with self._lock:
                        self.total -= 1
            """,
    }, "lock-discipline")
    assert findings == []


def test_lock_discipline_lock_released_before_await_is_clean():
    findings = _run({
        "production_stack_tpu/router/service.py": """\
            class Worker:
                async def step(self):
                    with self._lock:
                        payload = self.queue.pop()
                    await self.send(payload)
            """,
    }, "lock-discipline")
    assert findings == []


# ---- endpoint-contract -------------------------------------------------


def test_endpoint_contract_catches_every_drift_direction():
    findings = _run({
        "production_stack_tpu/engine/server.py": """\
            def build(app, h):
                app.router.add_get("/health", h)
                app.router.add_post("/v1/completions", h)
            """,
        "production_stack_tpu/engine/cache_server.py": """\
            def build(app, h):
                app.router.add_get("/stats", h)
            """,
        "production_stack_tpu/testing/fake_engine.py": """\
            FAKE_ENGINE_EXEMPT = {
                "GET /stats": "cache server runs in-process in tests",
                "GET /health": "redundant: the fake implements it",
                "POST /gone": "route no real server registers",
            }
            FAKE_ONLY_ROUTES = {
                "POST /fault": "fault injection hook",
            }

            def build(app, h):
                app.router.add_get("/health", h)
                app.router.add_post("/fault", h)
                app.router.add_post("/surprise", h)
            """,
    }, "endpoint-contract")
    messages = "\n".join(f.message for f in findings)
    assert "'POST /v1/completions' has no mirror" in messages
    assert ("FAKE_ENGINE_EXEMPT lists 'GET /health' but the fake "
            "implements it") in messages
    assert "stale exemption" in messages
    assert "fake-only route 'POST /surprise' is not declared" in messages
    # The correctly exempted and correctly declared routes are silent.
    assert "'GET /stats'" not in messages
    assert "'POST /fault'" not in messages


def test_endpoint_contract_accepts_mirrored_surface():
    findings = _run({
        "production_stack_tpu/engine/server.py": """\
            def build(app, h):
                app.router.add_get("/health", h)
            """,
        "production_stack_tpu/engine/cache_server.py": """\
            def build(app, h):
                pass
            """,
        "production_stack_tpu/testing/fake_engine.py": """\
            FAKE_ENGINE_EXEMPT = {}
            FAKE_ONLY_ROUTES = {}

            def build(app, h):
                app.router.add_get("/health", h)
            """,
    }, "endpoint-contract")
    assert findings == []


# ---- the real tree stays clean per new rule ----------------------------


def test_new_rules_are_clean_on_the_real_tree():
    project = Project.from_root(ROOT)
    for name in ("page-lifecycle", "state-machine", "lock-discipline",
                 "endpoint-contract"):
        findings = [f for f in run_rules(project, rules=[name])
                    if f.rule == name]
        assert findings == [], (
            f"{name} fired on the real tree:\n"
            + "\n".join(f.render() for f in findings))


# ---- CLI satellites: --diff, --sarif, baseline hygiene -----------------


def test_diff_parse_and_filter():
    text = textwrap.dedent("""\
        diff --git a/pkg/a.py b/pkg/a.py
        --- a/pkg/a.py
        +++ b/pkg/a.py
        @@ -10,0 +11,2 @@ def f():
        +    x = 1
        +    y = 2
        @@ -30 +33 @@ def g():
        +    z = 3
        diff --git a/pkg/b.py b/pkg/b.py
        --- a/pkg/b.py
        +++ b/pkg/b.py
        @@ -5,2 +0,0 @@ def h():
        """)
    changed = diff_mod.parse_unified_diff(text)
    assert changed["pkg/a.py"] == {11, 12, 33}
    assert changed["pkg/b.py"] == set()  # deletions: touched, no lines

    def f(path, line):
        return Finding(rule="r", path=path, line=line, message="m")

    kept = diff_mod.filter_findings(
        [f("pkg/a.py", 11), f("pkg/a.py", 20), f("pkg/a.py", 0),
         f("pkg/b.py", 7), f("pkg/b.py", 0), f("pkg/c.py", 1)],
        changed)
    assert [(x.path, x.line) for x in kept] == [
        ("pkg/a.py", 11),   # on a changed line
        ("pkg/a.py", 0),    # file-level contract finding, file touched
        ("pkg/b.py", 0),    # ditto (deletion-only touch)
    ]


def test_sarif_render_shape_and_fingerprints():
    from production_stack_tpu.staticcheck.core import REGISTRY
    import production_stack_tpu.staticcheck.analyzers  # noqa: F401
    finding = Finding(rule="state-machine", path="pkg/a.py", line=4,
                      message="planted")
    doc = sarif_mod.render([finding], REGISTRY)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "production-stack-tpu-staticcheck"
    assert {r["id"] for r in driver["rules"]} == set(REGISTRY)
    (result,) = run["results"]
    assert result["ruleId"] == "state-machine"
    assert driver["rules"][result["ruleIndex"]]["id"] == "state-machine"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "pkg/a.py"
    assert loc["region"]["startLine"] == 4
    assert (result["partialFingerprints"]["staticcheckFingerprint/v1"]
            == finding.fingerprint())


def test_baseline_prune_and_stale_detection(tmp_path):
    live = Finding(rule="r", path="a.py", line=1, message="still here")
    dead = Finding(rule="r", path="b.py", line=2, message="paid down")
    (tmp_path / "production_stack_tpu" / "staticcheck").mkdir(
        parents=True)
    baseline_mod.write(tmp_path, [live, dead])

    stale = baseline_mod.stale_entries(tmp_path, [live])
    assert [e["fingerprint"] for e in stale] == [dead.fingerprint()]

    dropped = baseline_mod.prune(tmp_path, [live])
    assert [e["fingerprint"] for e in dropped] == [dead.fingerprint()]
    kept = baseline_mod.load_fingerprints(tmp_path)
    assert kept == {live.fingerprint()}
    # Idempotent: nothing stale remains.
    assert baseline_mod.stale_entries(tmp_path, [live]) == []
    assert baseline_mod.prune(tmp_path, [live]) == []


# ---- runtime regressions for the drift the rules surfaced --------------


def test_sequence_transition_guards_untabled_moves():
    import pytest
    from production_stack_tpu.engine.sequence import (
        SamplingParams, Sequence, SequenceState,
    )
    seq = Sequence(seq_id="s1", prompt_token_ids=[1, 2],
                   sampling=SamplingParams())
    assert seq.state == SequenceState.WAITING
    seq.transition(SequenceState.RUNNING)
    assert seq.state == SequenceState.RUNNING
    seq.transition(SequenceState.RUNNING)  # same-state no-op
    assert seq.state == SequenceState.RUNNING
    seq.transition(SequenceState.FINISHED)
    with pytest.raises(ValueError, match="untabled sequence transition"):
        seq.transition(SequenceState.RUNNING)
    assert seq.state == SequenceState.FINISHED  # guard left state alone


def test_fake_engine_serves_version_like_the_real_server():
    from aiohttp.test_utils import TestClient, TestServer
    from production_stack_tpu.testing.fake_engine import (
        build_fake_engine,
    )
    from production_stack_tpu.version import __version__

    async def run():
        client = TestClient(TestServer(build_fake_engine()))
        await client.start_server()
        try:
            resp = await client.get("/version")
            assert resp.status == 200
            # The identity fields of EngineServer.version: the build
            # identity rides along so rollouts can verify a canary's
            # revision (docs/fleet.md); empty when no --build-id was
            # given. (The real server also names its device.) And the
            # start's spans, from the real server's class.
            reply = await resp.json()
            assert reply.pop("startup")["spans"][0]["name"] == "boot"
            assert reply == {"version": __version__, "build_id": ""}
        finally:
            await client.close()

    asyncio.run(run())


def test_fake_engine_debug_steps_mirrors_real_contract():
    from aiohttp.test_utils import TestClient, TestServer
    from production_stack_tpu.testing.fake_engine import (
        build_fake_engine,
    )

    async def run():
        # Flight recorder on (the default): shape contract.
        client = TestClient(TestServer(build_fake_engine()))
        await client.start_server()
        try:
            resp = await client.get("/debug/steps")
            assert resp.status == 200
            data = await resp.json()
            assert isinstance(data["steps"], list)

            resp = await client.get("/debug/steps?limit=notanint")
            assert resp.status == 400
            data = await resp.json()
            assert "limit must be an integer" in data["error"]["message"]
        finally:
            await client.close()

        # Tracing disabled: same 404 contract as the real server.
        client = TestClient(TestServer(
            build_fake_engine(trace_ring=0)))
        await client.start_server()
        try:
            resp = await client.get("/debug/steps")
            assert resp.status == 404
            data = await resp.json()
            assert "tracing disabled" in data["error"]["message"]
        finally:
            await client.close()

    asyncio.run(run())
