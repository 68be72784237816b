"""A configuration's family: the one name by which the harness finds
what it must know of an architecture.

``"family": "<name>"`` in a configuration's ``chipbench`` group names
two modules: ``chipbench/reference/<name>.py``, the plain float32
forward pass (``program_model`` and ``log_probs``; ``reference/check.py``
uses those two and nothing else of a family), and
``chipbench/counts/<name>.py``, the operations and bytes the algorithm
needs (``decode_step_bytes`` and ``prefill_flops``, which ``roofline.py``
hands on to).  Nothing in the harness names a family; a new one brings
its two files and its name.  There is no default: a configuration that
does not say what it is cannot be checked or counted.
"""

from __future__ import annotations

import importlib
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
KINDS = ("reference", "counts")


class UnknownFamily(ValueError):
    pass


def name_of(config: dict) -> str:
    """The family a configuration (a configuration file's content)
    names, once both of its modules are seen to be there."""
    bench = config.get("chipbench", {})
    family = bench.get("family")
    if not isinstance(family, str) or not family.isidentifier():
        raise UnknownFamily(
            f"configuration {bench.get('name')!r} names no family: add "
            '"family": "<name>" to its "chipbench" group, where '
            "chipbench/reference/<name>.py is its float32 reference and "
            "chipbench/counts/<name>.py its roofline counts")
    for kind in KINDS:
        path = os.path.join(BENCH, kind, family + ".py")
        if not os.path.exists(path):
            raise UnknownFamily(
                f"configuration {bench.get('name')!r} names the family "
                f"{family!r}, and there is no chipbench/{kind}/{family}.py")
    return family


def module(kind: str, config: dict):
    """``chipbench.<kind>.<family>`` of a configuration; ``kind`` is
    ``"reference"`` (imports jax) or ``"counts"`` (does not)."""
    return importlib.import_module(f"chipbench.{kind}.{name_of(config)}")
