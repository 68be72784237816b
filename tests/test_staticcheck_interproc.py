"""Self-tests for the interprocedural staticcheck layer (tier 1).

Covers the PR 20 surface: the call graph (resolution kinds, honest
unresolved edges, SCCs), bottom-up function summaries (may-block /
may-host-sync chains, may-raise, page custody, returns-alloc), the
four migrated transitive rules (planted + clean fixture pairs each,
including the soundness obligation that an unresolved edge never
manufactures a finding), the new ``shape-flow`` recompile-budget
proof, chain capping, waiver expiry, fingerprint stability across a
pure rename, and ``--jobs`` output parity. Fixtures are in-memory
(``Project.from_sources``), never the real tree — the real tree's
cleanliness is asserted separately in test_staticcheck.py.
"""

import datetime
import textwrap

from production_stack_tpu.staticcheck import (
    Project,
    run_rules,
)
from production_stack_tpu.staticcheck import callgraph, summaries
from production_stack_tpu.staticcheck.core import (
    CHAIN_CAP,
    cap_frames,
    render_chain,
    _waiver_findings,
)


def _project(sources):
    return Project.from_sources(
        {path: textwrap.dedent(text)
         for path, text in sources.items()})


def _run(sources, rule):
    return [f for f in run_rules(_project(sources), rules=[rule])
            if f.rule == rule]


# ---- call graph --------------------------------------------------------


def test_callgraph_resolves_direct_method_alias_and_partial():
    project = _project({
        "production_stack_tpu/a.py": """\
            import functools
            from production_stack_tpu.b import helper

            def local():
                pass

            class C:
                def m(self):
                    self.n()
                    local()
                    helper()
                    h = functools.partial(local, 1)
                    h()

                def n(self):
                    pass
        """,
        "production_stack_tpu/b.py": """\
            def helper():
                pass
        """,
    })
    graph = callgraph.for_project(project)
    edges = {e.target_text: e
             for e in graph.edges_from(
                 "production_stack_tpu/a.py::C.m")}
    assert edges["self.n"].callee == "production_stack_tpu/a.py::C.n"
    assert edges["self.n"].kind == "method"
    assert edges["local"].callee == "production_stack_tpu/a.py::local"
    assert edges["helper"].callee == "production_stack_tpu/b.py::helper"
    assert edges["h"].callee == "production_stack_tpu/a.py::local"
    assert edges["h"].kind == "alias"


def test_callgraph_keeps_unknown_receivers_unresolved():
    project = _project({
        "production_stack_tpu/a.py": """\
            def f(obj):
                obj.method()
                callback = obj.pick()
                callback()
        """,
    })
    graph = callgraph.for_project(project)
    edges = graph.edges_from("production_stack_tpu/a.py::f")
    assert edges, "calls must be recorded even when unresolved"
    assert all(e.callee is None for e in edges)
    assert any(e.kind == "unresolved" for e in edges)


def test_callgraph_sccs_are_reverse_topological():
    project = _project({
        "production_stack_tpu/a.py": """\
            def leaf():
                pass

            def mid():
                leaf()

            def top():
                mid()

            def ping():
                pong()

            def pong():
                ping()
        """,
    })
    graph = callgraph.for_project(project)
    sccs = graph.sccs()
    order = {qual: i for i, scc in enumerate(sccs) for qual in scc}
    a = "production_stack_tpu/a.py::"
    assert order[a + "leaf"] < order[a + "mid"] < order[a + "top"]
    # The mutual recursion collapses into one SCC of size 2.
    cycle = [scc for scc in sccs if len(scc) == 2]
    assert cycle and set(cycle[0]) == {a + "ping", a + "pong"}


# ---- summaries ---------------------------------------------------------


def test_summaries_chain_reaches_through_two_helpers():
    project = _project({
        "production_stack_tpu/a.py": """\
            def outer():
                return inner()

            def inner():
                import time
                time.sleep(1)
        """,
    })
    sums = summaries.for_project(project)
    chain = sums.get("production_stack_tpu/a.py::outer").may_block
    assert chain is not None
    assert [frame[2] for frame in chain][-1].startswith("time.sleep")


def test_summaries_recursion_converges_to_shortest_chain():
    project = _project({
        "production_stack_tpu/a.py": """\
            def ping(n):
                pong(n)

            def pong(n):
                ping(n)
                open("x")
        """,
    })
    sums = summaries.for_project(project)
    pong = sums.get("production_stack_tpu/a.py::pong").may_block
    ping = sums.get("production_stack_tpu/a.py::ping").may_block
    # pong blocks directly (1 frame); ping via pong (2 frames) — the
    # cycle must not inflate either chain.
    assert pong is not None and len(pong) == 1
    assert ping is not None and len(ping) == 2


def test_summaries_consumed_vs_noncustodial_params():
    project = _project({
        "production_stack_tpu/a.py": """\
            def stores(seq, pages):
                seq.pages = pages

            def reads(pages):
                print(len(pages))

            def forwards_to_reader(pages):
                reads(pages)

            def forwards_to_unknown(pages, sink):
                sink.push(pages)
        """,
    })
    sums = summaries.for_project(project)
    a = "production_stack_tpu/a.py::"
    assert "pages" in sums.get(a + "stores").consumed_params
    assert "pages" not in sums.get(a + "reads").consumed_params
    assert "pages" not in sums.get(
        a + "forwards_to_reader").consumed_params
    # Unknown callee => must assume custody (soundness stance).
    assert "pages" in sums.get(
        a + "forwards_to_unknown").consumed_params


def test_summaries_returns_alloc_through_helper():
    project = _project({
        "production_stack_tpu/a.py": """\
            def direct(cache, n):
                return cache.allocate_pages(n)

            def wrapped(cache, n):
                return list(direct(cache, n))

            def unrelated(cache):
                return cache.stats()
        """,
    })
    sums = summaries.for_project(project)
    a = "production_stack_tpu/a.py::"
    assert sums.get(a + "direct").returns_alloc
    assert sums.get(a + "wrapped").returns_alloc
    assert not sums.get(a + "unrelated").returns_alloc


def test_summaries_may_raise_propagates():
    project = _project({
        "production_stack_tpu/a.py": """\
            def thrower():
                raise ValueError("boom")

            def caller():
                thrower()
        """,
    })
    sums = summaries.for_project(project)
    a = "production_stack_tpu/a.py::"
    assert "ValueError" in sums.get(a + "thrower").may_raise
    assert "ValueError" in sums.get(a + "caller").may_raise


# ---- transitive async-blocking -----------------------------------------

_ASYNC_HELPERS = {
    "production_stack_tpu/router/util.py": """\
        def read_config(path):
            return _load(path)

        def _load(path):
            with open(path) as f:
                return f.read()
    """,
}


def test_async_blocking_transitive_flags_handler_not_sync_caller():
    findings = _run({
        **_ASYNC_HELPERS,
        "production_stack_tpu/router/app.py": """\
            from production_stack_tpu.router.util import read_config

            async def handler(request):
                return read_config("x.json")

            def sync_caller():
                return read_config("y.json")
        """,
    }, "async-blocking")
    assert len(findings) == 1
    f = findings[0]
    assert "handler" in f.message
    assert "read_config" in f.message
    assert "open()" in f.message       # blocking primitive, 2 frames down
    assert len(f.chain) >= 3


def test_async_blocking_transitive_clean_through_async_helper():
    findings = _run({
        "production_stack_tpu/router/app.py": """\
            import asyncio

            async def helper():
                await asyncio.sleep(1)

            async def handler(request):
                await helper()
        """,
    }, "async-blocking")
    assert findings == []


def test_async_blocking_unresolved_edge_makes_no_finding():
    findings = _run({
        "production_stack_tpu/router/app.py": """\
            async def handler(request, client):
                client.fetch_sync()
        """,
    }, "async-blocking")
    assert findings == []


# ---- transitive tracer-hygiene / host-read -----------------------------


def test_tracer_hygiene_transitive_sync_below_jit_boundary():
    findings = _run({
        "production_stack_tpu/ops/kern.py": """\
            import jax

            def _peek(x):
                return x.item()

            @jax.jit
            def step(x):
                return _peek(x)
        """,
    }, "tracer-hygiene")
    transitive = [f for f in findings if "reaches a" in f.message]
    assert len(transitive) == 1
    assert "_peek" in transitive[0].message


def test_tracer_hygiene_transitive_clean_helper_not_flagged():
    findings = _run({
        "production_stack_tpu/ops/kern.py": """\
            import jax
            import jax.numpy as jnp

            def _scale(x):
                return x * 2

            @jax.jit
            def step(x):
                return _scale(x)
        """,
    }, "tracer-hygiene")
    assert findings == []


def test_host_read_transitive_helper_below_dispatch_path():
    findings = _run({
        "production_stack_tpu/engine/model_runner.py": """\
            import jax

            def dispatch_decode(rows):
                return _staging_set(rows)

            def _staging_set(rows):
                return _peek_helper(rows)

            def _dispatch(payload):
                return payload

            def execute_payload(payload):
                return payload

            def _optional_device_inputs(p):
                return p

            def _penalty_payload(p):
                return p

            def _seed_payload(p):
                return p

            def _bias_payload(p):
                return p

            def _suppress_payload(p):
                return p

            def _guided_payload(p):
                return p

            def _next_rng():
                return 1

            def _as_device(x):
                return x

            def _peek_helper(rows):
                return jax.device_get(rows)
        """,
    }, "host-read")
    transitive = [f for f in findings
                  if "reaches a blocking host read" in f.message]
    assert len(transitive) == 1
    assert "_peek_helper" in transitive[0].message


# ---- transitive page-lifecycle -----------------------------------------


def test_page_lifecycle_alloc_via_helper_summary():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            class Scheduler:
                def _grab(self, n):
                    return self.cache.allocate_pages(n)

                def admit(self, seq):
                    pages = self._grab(4)
                    if not seq.ok:
                        return None
                    seq.pages = pages
                    return pages
        """,
    }, "page-lifecycle")
    assert len(findings) == 1
    assert "pages" in findings[0].message


def test_page_lifecycle_pure_read_callee_does_not_take_custody():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            class Scheduler:
                def admit(self, seq):
                    pages = self.cache.allocate_pages(4)
                    self._log_count(pages)
                    return None

                def _log_count(self, pages):
                    print(len(pages))
        """,
    }, "page-lifecycle")
    assert len(findings) == 1  # the len() read proves nothing owned


def test_page_lifecycle_consuming_callee_takes_custody():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            class Scheduler:
                def admit(self, seq):
                    pages = self.cache.allocate_pages(4)
                    self._attach(seq, pages)
                    return None

                def _attach(self, seq, pages):
                    seq.pages = pages
        """,
    }, "page-lifecycle")
    assert findings == []


def test_page_lifecycle_unresolved_callee_counts_as_custody():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            class Scheduler:
                def admit(self, seq):
                    pages = self.cache.allocate_pages(4)
                    seq.take(pages)
                    return None
        """,
    }, "page-lifecycle")
    assert findings == []


def test_page_lifecycle_callee_may_raise_creates_exception_path():
    findings = _run({
        "production_stack_tpu/engine/scheduler.py": """\
            class Scheduler:
                def _check(self, seq):
                    if not seq.ok:
                        raise ValueError("bad")

                def admit(self, seq):
                    pages = self.cache.allocate_pages(4)
                    self._check(seq)
                    seq.pages = pages
        """,
    }, "page-lifecycle")
    assert len(findings) == 1
    assert "exception path" in findings[0].message


# ---- shape-flow --------------------------------------------------------

_RUNNER_HEADER = """\
    import jax

    class Runner:
        def __init__(self):
            self._step_jit = jax.jit(self._impl)
            self._buckets = [16, 32, 64]

        def _bucket_for(self, n):
            for b in self._buckets:
                if n <= b:
                    return b
            return self._buckets[-1]

"""


def test_shape_flow_flags_unsnapped_int_through_helper():
    findings = _run({
        "production_stack_tpu/engine/runner.py":
            _RUNNER_HEADER + """\
        def dispatch(self, rows):
            n = self._pick_width(rows)
            return self._step_jit(self.params, n)

        def _pick_width(self, rows):
            return len(rows)
""",
    }, "shape-flow")
    assert len(findings) == 1
    f = findings[0]
    assert "_pick_width" in f.message
    assert "len(" in f.message
    assert len(f.chain) >= 3


def test_shape_flow_flags_raw_param_from_caller():
    findings = _run({
        "production_stack_tpu/engine/runner.py":
            _RUNNER_HEADER + """\
        def inner_dispatch(self, w):
            return self._step_jit(self.params, w)

        def outer(self, rows):
            return self.inner_dispatch(len(rows))
""",
    }, "shape-flow")
    assert len(findings) == 1
    assert "passes w" in findings[0].message


def test_shape_flow_accepts_snap_helper_and_inline_lattice():
    findings = _run({
        "production_stack_tpu/engine/runner.py":
            _RUNNER_HEADER + """\
        def snapped(self, rows):
            t = self._bucket_for(len(rows))
            return self._step_jit(self.params, t)

        def lattice(self, rows):
            t = 16
            while t < len(rows):
                t *= 2
            return self._step_jit(self.params, t)

        def config(self, rows):
            return self._step_jit(self.params, self.decode_width)
""",
    }, "shape-flow")
    assert findings == []


def test_shape_flow_unresolved_call_makes_no_finding():
    findings = _run({
        "production_stack_tpu/engine/runner.py":
            _RUNNER_HEADER + """\
        def opaque(self, payload):
            return self._step_jit(self.params, payload.width())
""",
    }, "shape-flow")
    assert findings == []


def test_shape_flow_shape_source_waiver_suppresses():
    findings = _run({
        "production_stack_tpu/engine/runner.py":
            _RUNNER_HEADER + """\
        def declared(self, rows):
            n = len(rows)  # lint: shape-source
            return self._step_jit(self.params, n)
""",
    }, "shape-flow")
    assert findings == []


# ---- chain capping -----------------------------------------------------


def test_cap_frames_caps_at_chain_cap_and_counts_dropped():
    frames = [("f.py", i, f"frame{i}") for i in range(10)]
    capped, dropped = cap_frames(frames)
    assert len(capped) == CHAIN_CAP
    assert dropped == 10 - CHAIN_CAP
    rendered = render_chain(frames)
    assert f"… (+{10 - CHAIN_CAP} frames)" in rendered
    assert rendered.count("→") == CHAIN_CAP - 1


def test_deep_chain_is_capped_in_finding_json():
    helpers = {}
    # h0 -> h1 -> ... -> h9 -> open(): a 10-frame blocking chain.
    body = "def h9(p):\n    with open(p) as f:\n        return f.read()\n"
    for i in range(9):
        body += f"\n\ndef h{8 - i}(p):\n    return h{9 - i}(p)\n"
    findings = _run({
        "production_stack_tpu/router/util.py": body,
        "production_stack_tpu/router/app.py": """\
            from production_stack_tpu.router.util import h0

            async def handler(request):
                return h0("x")
        """,
    }, "async-blocking")
    assert len(findings) == 1
    payload = findings[0].to_json()
    assert len(payload["chain"]) == CHAIN_CAP
    assert payload["chain_dropped"] > 0
    assert "… (+" in findings[0].message


# ---- waiver expiry -----------------------------------------------------


def test_dated_waiver_suppresses_until_expiry():
    # The clock is injected: a date built from the day the test was
    # written stops being the future.
    today = datetime.date(2026, 8, 6)
    until = (today + datetime.timedelta(days=30)).isoformat()
    project = _project({
        "production_stack_tpu/router/app.py": f"""\
            import time

            async def handler(request):
                time.sleep(1)  # lint: allow-async-blocking until={until}
        """,
    })
    assert run_rules(project, rules=["async-blocking"],
                     today=today) == []
    # The day after it lapses, the finding and the lapse both surface.
    late = today + datetime.timedelta(days=31)
    assert {f.rule for f in run_rules(
        project, rules=["async-blocking"], today=late)} == {
            "async-blocking", "expired-waiver"}


def test_expired_waiver_stops_suppressing_and_is_reported():
    project = _project({
        "production_stack_tpu/router/app.py": """\
            import time

            async def handler(request):
                time.sleep(1)  # lint: allow-async-blocking until=2025-01-01
        """,
    })
    findings = run_rules(project)
    rules_hit = {f.rule for f in findings}
    assert "async-blocking" in rules_hit    # suppression lapsed
    assert "expired-waiver" in rules_hit    # and the lapse is loud
    expired = [f for f in findings if f.rule == "expired-waiver"]
    assert "2025-01-01" in expired[0].message


def test_malformed_waiver_date_is_a_finding():
    project = _project({
        "production_stack_tpu/router/app.py": """\
            import time

            async def handler(request):
                time.sleep(1)  # lint: allow-async-blocking until=soon
        """,
    })
    findings = _waiver_findings(project)
    assert any(f.rule == "expired-waiver" and "soon" in f.message
               for f in findings)


# ---- fingerprint stability ---------------------------------------------


def test_transitive_fingerprint_survives_pure_helper_rename():
    def tree(helper_name):
        return {
            "production_stack_tpu/router/app.py": f"""\
                from production_stack_tpu.router.util import (
                    {helper_name},
                )

                async def handler(request):
                    return {helper_name}()
            """,
            "production_stack_tpu/router/util.py": f"""\
                def {helper_name}():
                    import time
                    time.sleep(1)
            """,
        }
    # The flagged line's *text* is unchanged modulo the rename; the
    # fingerprint normalizes neither chain nor line numbers into the
    # hash, so line drift above the call site must not move it.
    before = _run(tree("read_config"), "async-blocking")
    drifted = {
        path: ("# a new leading comment\n\n"
               + textwrap.dedent(text) if "app" in path
               else text)
        for path, text in tree("read_config").items()}
    after = _run(drifted, "async-blocking")
    assert len(before) == len(after) == 1
    assert before[0].fingerprint() == after[0].fingerprint()


# ---- --jobs parity -----------------------------------------------------


def test_jobs_parallel_run_matches_serial_run():
    sources = {
        **_ASYNC_HELPERS,
        "production_stack_tpu/router/app.py": """\
            from production_stack_tpu.router.util import read_config

            async def handler(request):
                return read_config("x.json")
        """,
        "production_stack_tpu/engine/scheduler.py": """\
            class Scheduler:
                def admit(self, seq):
                    pages = self.cache.allocate_pages(4)
                    if not seq.ok:
                        return None
                    seq.pages = pages
        """,
    }
    serial = run_rules(_project(sources))
    parallel = run_rules(_project(sources), jobs=4)
    assert [f.to_json() for f in serial] == \
        [f.to_json() for f in parallel]
    assert serial, "fixture must actually produce findings"
