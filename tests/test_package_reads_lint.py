"""Static check: the package reaches for no file outside itself.

``production_stack_tpu/`` is what gets installed; a path built with
``".."`` from a module's own location, or one that names the
repository's ``benchmarks`` directory, reads the source tree around
the package. The runner once chose a kernel from a results file found
that way. Docstrings may name the directory; code may not.
"""

import ast
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = ROOT / "production_stack_tpu"


def _docstrings(tree):
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                found.add(id(body[0].value))
    return found


def _violations(source, name):
    tree = ast.parse(source, filename=name)
    docstrings = _docstrings(tree)
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and ast.unparse(node.func).endswith("path.join")
                and any(isinstance(a, ast.Constant) and a.value == ".."
                        for a in node.args)):
            out.append(f"{name}:{node.lineno}: os.path.join(..., '..')")
        if (isinstance(node, ast.Constant)
                and node.value == "benchmarks"
                and id(node) not in docstrings):
            out.append(f"{name}:{node.lineno}: the literal 'benchmarks'")
    return out


def test_package_builds_no_path_out_of_itself():
    # The checker flags what it exists for (the parent's form).
    planted = ("import os\n"
               "p = os.path.join(os.path.dirname(__file__), '..', '..',\n"
               "                 'benchmarks', 'results', 'x.json')\n")
    assert len(_violations(planted, "planted.py")) == 2
    assert not _violations('"""Reads benchmarks/ never."""\n', "ok.py")

    findings = [v for path in sorted(PACKAGE.rglob("*.py"))
                for v in _violations(path.read_text(),
                                     str(path.relative_to(ROOT)))]
    assert not findings, "\n".join(findings)
