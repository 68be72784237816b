"""Rule ``host-read``: no blocking host reads on the dispatch path.

The overlapped async pipeline (docs/async_pipeline.md) only hides
host work if ``ModelRunner.dispatch_decode`` and everything it calls
stays purely dispatching: building a payload, one fused host->device
transfer, launching the jitted step. The served loop leans on the same
property of every step program's enqueue side (``dispatch_burst``,
``dispatch_prefill``, ``dispatch_spec``, ``dispatch_unified``): the
turn before's outputs are made and handed over behind the dispatch,
so a read-back that creeps in between two programs (as the sampling
key's did until PR 48) leaves the device idle for its round trip. A
single ``np.asarray(device array)``, ``jax.device_get`` or
``.block_until_ready()`` anywhere on that path silently re-serializes
the pipeline — the step "works" but the overlap is gone, which no
functional test notices. Inside the DISPATCH_PATH functions of
engine/model_runner.py this flags:

- ``np.asarray(...)`` / ``np.array(...)`` — *unless* the argument is
  provably host-origin (see below): converting a Python list is a
  plain host op, not a device sync,
- ``jax.device_get(...)`` / ``device_get(...)``,
- ``<anything>.block_until_ready()`` and ``<array>.item()``.

Host-origin is decided flow-sensitively over the CFG
(staticcheck/cfg.py) with a must-analysis (staticcheck/dataflow.py,
intersection join): an argument is host-origin when it is a literal,
a known host-list attribute of a sequence (``seq.output_token_ids``,
``seq.prompt_token_ids``, ...), a ``list()``/``range()``/``sorted()``
result, or a local name assigned only such values on **every** path
reaching the call. Anything a device value could flow into stays
flagged. This is what used to require ``# lint: allow-host-read``
waivers on the penalty-payload asarray calls — the dataflow now
proves those reads safe instead.

``int(...)`` / ``float(...)`` of host scalars are fine and not
flagged. A deliberate device read still carries
``# lint: allow-host-read`` on the call line. The DISPATCH_PATH set
must track reality: a listed name missing from model_runner.py is
itself a finding, so a renamed function cannot silently fall out of
coverage.

**Transitive variant** (interprocedural, PR 20): a helper called
from a DISPATCH_PATH function whose summary says it may host-sync
(``.item()`` / ``device_get`` / ``.block_until_ready()`` anywhere in
its resolved call tree) is flagged at the dispatch-path call site
with the full chain — the blocking read re-serializes the pipeline
no matter how many frames down it hides. Resolved edges only; an
unresolved edge never manufactures a finding.

Migrated from tests/test_dispatch_path_lint.py (PR 3), now a thin
wrapper over this rule.
"""

from __future__ import annotations

import ast
from typing import FrozenSet, List

from production_stack_tpu.staticcheck.cfg import CFG
from production_stack_tpu.staticcheck.core import (
    Finding,
    Project,
    recv_name,
    render_chain,
    rule,
    tail_name,
)
from production_stack_tpu.staticcheck import (
    callgraph,
    dataflow,
    summaries,
)

RUNNER = "production_stack_tpu/engine/model_runner.py"

# Every function a dispatch runs through: the async pipeline's
# (dispatch_decode) and, since the served loop hands a turn's outputs
# over behind the next program's dispatch (docs/async_pipeline.md,
# "The served loop"), the enqueue side of every other step program.
# result() / read_back() are NOT here: they are the completion side
# and their device_get is the one intended blocking read.
DISPATCH_PATH = {
    "dispatch_decode",
    "dispatch_decode_plan",
    "dispatch_burst",
    "dispatch_prefill",
    "dispatch_sp_prefill",
    "dispatch_spec",
    "dispatch_unified",
    "_page_table_rows",
    "_state_slot_rows",
    "_note_attn_pages",
    "_other_width_payloads",
    "_staging_set",
    "_dispatch",
    "execute_payload",
    "_optional_device_inputs",
    "_penalty_payload",
    "_seed_payload",
    "_bias_payload",
    "_suppress_payload",
    "_guided_payload",
    "_next_rng",
    "_as_device",
}

# Attributes that are host Python lists/scalars by construction
# (engine/sequence.py): reading them never touches the device.
HOST_ATTRS = {
    "output_token_ids", "prompt_token_ids", "all_token_ids",
    "stop_token_ids", "pages", "num_computed_tokens",
    "num_prior_output_tokens", "seq_id", "sampling",
}

# Builtins whose result is host data when their inputs are.
_HOST_CALLS = {"list", "tuple", "range", "sorted", "len", "int",
               "float", "min", "max", "sum", "enumerate", "zip"}


def _is_host_expr(node: ast.AST, host_names: FrozenSet[str]) -> bool:
    """Conservative proof that ``node`` is host data (never a device
    array)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.List, ast.Tuple, ast.Set, ast.ListComp,
                         ast.SetComp, ast.GeneratorExp)):
        return True
    if isinstance(node, ast.Name):
        return node.id in host_names
    if isinstance(node, ast.Attribute):
        return node.attr in HOST_ATTRS
    if isinstance(node, ast.Subscript):
        return _is_host_expr(node.value, host_names)
    if isinstance(node, ast.BinOp):
        return (_is_host_expr(node.left, host_names)
                and _is_host_expr(node.right, host_names))
    if isinstance(node, ast.Call):
        return (isinstance(node.func, ast.Name)
                and node.func.id in _HOST_CALLS
                and all(_is_host_expr(a, host_names)
                        for a in node.args))
    return False


def _host_transfer(state: FrozenSet[str], el, _kind) -> FrozenSet[str]:
    if isinstance(el, ast.Assign):
        names = [t.id for t in el.targets if isinstance(t, ast.Name)]
        if names:
            if _is_host_expr(el.value, state):
                return state | frozenset(names)
            return state - frozenset(names)
    elif isinstance(el, ast.AugAssign) and isinstance(
            el.target, ast.Name):
        if not _is_host_expr(el.value, state):
            return state - {el.target.id}
    elif isinstance(el, (ast.For, ast.AsyncFor)):
        targets = frozenset(n.id for n in ast.walk(el.target)
                            if isinstance(n, ast.Name))
        if _is_host_expr(el.iter, state):
            return state | targets
        return state - targets
    return state


def is_blocking_call(call: ast.Call) -> bool:
    func = call.func
    name = tail_name(func)
    recv = recv_name(func)
    if recv == "np" and name in ("asarray", "array"):
        return True
    if name == "device_get":  # jax.device_get or bare import
        return True
    if isinstance(func, ast.Attribute) and name in (
            "block_until_ready", "item"):
        return True
    return False


def _host_exempt(call: ast.Call, host_names: FrozenSet[str]) -> bool:
    """np.asarray/np.array of provably-host data is a plain host op."""
    if recv_name(call.func) != "np":
        return False
    if tail_name(call.func) not in ("asarray", "array"):
        return False
    return bool(call.args) and _is_host_expr(call.args[0], host_names)


def dispatch_path_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in DISPATCH_PATH:
                yield node


def _transitive_findings(project: Project, sf, fn) -> List[Finding]:
    """Host syncs hidden below a dispatch-path function boundary."""
    graph = callgraph.for_project(project)
    sums = summaries.for_project(project)
    info = graph.function_at(sf.relpath, fn)
    if info is None:
        return []
    findings: List[Finding] = []
    for edge in graph.resolved_edges_from(info.qual):
        callee_info = graph.functions.get(edge.callee)
        if callee_info is None or callee_info.name in DISPATCH_PATH:
            continue  # covered by its own dispatch-path scan
        summary = sums.get(edge.callee)
        if summary.may_host_sync is None:
            continue
        if is_blocking_call(edge.call):
            continue  # the intraprocedural scan already flagged it
        chain = (
            (sf.relpath, edge.lineno, fn.name),
            (sf.relpath, edge.lineno, callee_info.label()),
        ) + summary.may_host_sync
        findings.append(sf.finding(
            "host-read", edge.call,
            f"call to {edge.target_text}() in dispatch-path "
            f"function {fn.name} reaches a blocking host read via "
            f"{render_chain(chain)} — it re-serializes the async "
            "pipeline (docs/async_pipeline.md)",
            chain=chain))
    return findings


@rule("host-read",
      "no blocking host reads inside the async dispatch path, "
      "including through helpers (transitive)",
      interprocedural=True)
def check(project: Project) -> List[Finding]:
    sf = project.source(RUNNER)
    if sf is None or sf.tree is None:
        return []
    findings: List[Finding] = []
    seen = set()
    for fn in dispatch_path_functions(sf.tree):
        seen.add(fn.name)
        findings.extend(_transitive_findings(project, sf, fn))
        cfg = CFG(fn, raises=lambda _s, _t: False)
        block_in, _ = dataflow.solve(
            cfg, frozenset(), _host_transfer, join="intersection")
        for block in cfg.reachable():
            if block.id not in block_in:
                continue
            state = block_in[block.id]
            for el in block.elements:
                if isinstance(el, ast.AST) and not isinstance(
                        el, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for node in ast.walk(el):
                        if (isinstance(node, ast.Call)
                                and is_blocking_call(node)
                                and not _host_exempt(node, state)):
                            findings.append(sf.finding(
                                "host-read", node,
                                f"blocking host read in {fn.name} "
                                "re-serializes the async pipeline — "
                                "move it to result()/completion "
                                "(docs/async_pipeline.md)"))
                state = _host_transfer(state, el, None)
    missing = DISPATCH_PATH - seen
    if missing:
        findings.append(Finding(
            rule="host-read", path=RUNNER, line=0,
            message="DISPATCH_PATH names not found in "
                    f"model_runner.py: {sorted(missing)} — update "
                    "staticcheck/analyzers/dispatch_path.py so the "
                    "lint tracks the real call graph"))
    return findings
