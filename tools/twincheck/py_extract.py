"""AST extractors over the Python twins for the twin-contract auditor.

Counterpart of c_extract.py: pulls the contract-bearing surfaces out of
`network/transport.py` (constants, fingerprint arity, congestion-control
registry, cubic arithmetic literals), `config/schema.py` (enum-name
duplicates), `checkpoint.py` (format VERSION), `network/unit.py` (unit
kinds), and the whole `shadow_tpu/` tree (counter-name string literals,
identifier vocabulary).  Extractors raise ExtractError when an anchor is
missing so a refactor that moves a contract surface fails the audit
loudly instead of silently narrowing it.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path


class ExtractError(Exception):
    pass


def parse(path) -> ast.Module:
    return ast.parse(Path(path).read_text(), filename=str(path))


# -- module constants ---------------------------------------------------------

def _eval_const(node: ast.AST, env: dict):
    """Evaluate an int-valued constant expression of literals, names in
    ``env``, and + - * // << >> (the shapes the twins use)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, bool)):
        return int(node.value)
    if isinstance(node, ast.Name):
        v = env.get(node.id)
        return v if isinstance(v, int) else None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _eval_const(node.operand, env)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        a = _eval_const(node.left, env)
        b = _eval_const(node.right, env)
        if a is None or b is None:
            return None
        op = node.op
        if isinstance(op, ast.Mult):
            return a * b
        if isinstance(op, ast.Add):
            return a + b
        if isinstance(op, ast.Sub):
            return a - b
        if isinstance(op, ast.FloorDiv):
            return a // b
        if isinstance(op, ast.LShift):
            return a << b
        if isinstance(op, ast.RShift):
            return a >> b
    return None


def module_constants(tree: ast.Module, env: dict = None) -> dict:
    """Top-level ``NAME = <int expr>`` assignments, evaluated with
    ``env`` as the starting name environment (accumulating, so later
    constants may reference earlier ones)."""
    out = dict(env or {})
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            target = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name) \
                and node.value is not None:
            target = node.target.id  # NAME: SomeType = <int expr>
        if target is not None:
            v = _eval_const(node.value, out)
            if v is not None:
                out[target] = v
    return out


def range_enum(tree: ast.Module) -> dict:
    """``A, B, C = range(n)`` at module level -> {"A": 0, "B": 1, ...}
    (network/unit.py's kind enum)."""
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Tuple)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Name)
                and node.value.func.id == "range"):
            names = [e.id for e in node.targets[0].elts
                     if isinstance(e, ast.Name)]
            return {n: i for i, n in enumerate(names)}
    raise ExtractError("no `A, B, ... = range(n)` enum found")


# -- classes and methods ------------------------------------------------------

def class_def(tree: ast.Module, name: str) -> ast.ClassDef:
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == name:
            return node
    raise ExtractError("class %r not found" % name)


def method_def(cls: ast.ClassDef, name: str) -> ast.FunctionDef:
    for node in cls.body:
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    raise ExtractError("method %s.%s not found" % (cls.name, name))


def class_attr(cls: ast.ClassDef, attr: str):
    for node in cls.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == attr \
                and isinstance(node.value, ast.Constant):
            return node.value.value
    raise ExtractError("class attr %s.%s not found" % (cls.name, attr))


def return_tuple_arity(fn: ast.FunctionDef) -> int:
    """Element count of the LAST ``return (a, b, ...)`` in the function
    (StreamEndpoint.fingerprint's shape)."""
    rets = [n for n in ast.walk(fn) if isinstance(n, ast.Return)
            and isinstance(n.value, ast.Tuple)]
    if not rets:
        raise ExtractError("%s has no tuple return" % fn.name)
    return len(rets[-1].value.elts)


def dict_literal_keys(tree: ast.Module, name: str) -> dict:
    """``NAME = {"k": Value, ...}`` -> {"k": "Value"} (value = the
    Name id, e.g. the class object assigned)."""
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == name \
                and isinstance(node.value, ast.Dict):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and isinstance(v, ast.Name):
                    out[k.value] = v.id
            return out
    raise ExtractError("dict literal %r not found" % name)


def string_tuple(tree: ast.Module, name: str) -> tuple:
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == name \
                and isinstance(node.value, (ast.Tuple, ast.List)):
            return tuple(e.value for e in node.value.elts
                         if isinstance(e, ast.Constant))
    raise ExtractError("string tuple %r not found" % name)


def int_literal_set(fn: ast.FunctionDef, env: dict, minval: int = 3) -> set:
    """Set of integer literals >= minval in the method body, with Name
    loads resolved through ``env`` (module constants) — the Python half
    of the cubic-arithmetic cross-check.  Shift amounts appear as their
    raw literal (`1 << 32` contributes 32), matching the C side's
    raw-token view."""
    out = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool) and node.value >= minval:
            out.add(node.value)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            v = env.get(node.id)
            if isinstance(v, int) and v >= minval:
                out.add(v)
    return out


# -- tree-wide scans ----------------------------------------------------------

def counter_names(py_files) -> set:
    """Every string literal used as ``<x>.add("name", ...)`` first
    argument across the tree — the Python counter-name vocabulary the C
    engine's fold tables must stay inside."""
    names = set()
    for path in py_files:
        try:
            tree = parse(path)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "add" and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                names.add(node.args[0].value)
    return names


_IDENT = re.compile(r"[A-Za-z_]\w*")


def identifier_vocab(py_files) -> set:
    """The identifier vocabulary of the Python tree (cheap regex scan).
    Used to verify every attribute name the C engine interns still
    exists somewhere in the Python twins — catches renames like
    `_uid_counter` -> something that would leave the C side reading a
    stale attribute."""
    vocab = set()
    for path in py_files:
        vocab.update(_IDENT.findall(Path(path).read_text()))
    return vocab
