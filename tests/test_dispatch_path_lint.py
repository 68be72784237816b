"""Static check: no blocking host reads on the decode dispatch path.

The overlapped async pipeline (docs/async_pipeline.md) only hides
host work if ``ModelRunner.dispatch_decode`` and everything it calls
stays purely dispatching — a single ``np.asarray(device array)``,
``jax.device_get`` or ``.block_until_ready()`` on that path silently
re-serializes the pipeline.

Since PR 5 this is a thin wrapper over the staticcheck ``host-read``
rule (production_stack_tpu/staticcheck/analyzers/dispatch_path.py),
which also owns the DISPATCH_PATH function list and the
tracks-reality check. Test names are kept so history stays
comparable. Waivers: ``# lint: allow-host-read`` on the call line.
"""

import pathlib

import pytest

from production_stack_tpu.staticcheck import Project, run_rules

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _findings(project):
    return [f for f in run_rules(project, rules=["host-read"])
            if f.rule == "host-read"]


def test_dispatch_path_has_no_blocking_host_reads():
    # Covers both halves of the old test: no blocking reads inside
    # the DISPATCH_PATH functions, and every DISPATCH_PATH name still
    # existing in model_runner.py (the rule emits a finding when one
    # falls out of the real call graph).
    findings = _findings(Project.from_root(ROOT))
    assert not findings, (
        "Blocking host reads inside the async dispatch path (these "
        "re-serialize the pipeline; move the read to result()/"
        "completion, or add a '# lint: allow-host-read' waiver with "
        "justification):\n" + "\n".join(f.render() for f in findings)
    )


def test_lint_catches_a_violation():
    """The checker itself must actually flag offending calls."""
    findings = _findings(Project.from_sources({
        "production_stack_tpu/engine/model_runner.py":
            "def dispatch_decode(self):\n"
            "    x = np.asarray(self._next_rng())\n"
            "    y = jax.device_get(x)\n"
            "    z = sampled.block_until_ready()\n"
            "    return int(x[0])\n",
    }))
    blocking = [f for f in findings
                if "blocking host read" in f.message]
    # np.asarray, device_get, block_until_ready — int() is not one.
    assert len(blocking) == 3
    # A clean dispatch body produces no blocking-read findings.
    clean = _findings(Project.from_sources({
        "production_stack_tpu/engine/model_runner.py":
            "def dispatch_decode(self):\n"
            "    return jax.device_put(tuple(x))\n",
    }))
    assert not [f for f in clean if "blocking host read" in f.message]


@pytest.mark.parametrize("enqueue", ["dispatch_burst", "dispatch_prefill"])
def test_a_read_back_on_an_enqueue_function_is_a_finding(enqueue):
    """The served loop hands the turn before's outputs over behind
    these dispatches (docs/async_pipeline.md, "The served loop"): a
    key read back from the device there, as until PR 48, is a round
    trip between two programs."""
    findings = _findings(Project.from_sources({
        "production_stack_tpu/engine/model_runner.py":
            f"def {enqueue}(self, plan):\n"
            "    payload = {'rng': np.asarray(self._split())}\n"
            "    sampled = self._dispatch(2, 4, payload)\n"
            "    return StepHandle(self, sampled, parse)\n",
    }))
    blocking = [f for f in findings if "blocking host read" in f.message]
    assert len(blocking) == 1 and enqueue in blocking[0].message
    clean = _findings(Project.from_sources({
        "production_stack_tpu/engine/model_runner.py":
            f"def {enqueue}(self, plan):\n"
            "    payload = {'rng': self._next_rng()}\n"
            "    sampled = self._dispatch(2, 4, payload)\n"
            "    return StepHandle(self, sampled, parse)\n",
    }))
    assert not [f for f in clean if "blocking host read" in f.message]
