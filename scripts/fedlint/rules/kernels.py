"""Kernel-twin parity (FED301/FED302/FED303).

Every ``src/repro/kernels/<name>/`` package pairs a Pallas kernel with a
pure-jnp oracle, and the equivalence tests diff the two.  That only means
anything while the twins keep matching call signatures:

* FED301 — package structure: ``ops.py``, ``ref.py``, ``<name>.py`` and
  ``__init__.py`` must exist, ``ref.py`` must define at least one public
  ``*_ref`` oracle, and ``<name>.py`` must actually invoke
  ``pl.pallas_call``.
* FED302 — signature parity: every public ``*_ref`` function needs a twin
  among the public functions of ``ops.py``/``<name>.py`` whose parameters
  are a superset of the oracle's, in the same relative order, with
  AST-identical defaults wherever both sides declare one.  Extra twin
  parameters must be optional or keyword-only (tuning knobs like
  ``blk_q``/``interpret``), so any oracle call shape is a valid twin call
  shape.
* FED303 — dispatch: ``ops.py`` must import the kernel module (the Pallas
  route) and resolve its mode through ``repro.kernels.interpret_mode``
  (compiled on an accelerator, interpreted on the CPU backend), and
  ``__init__.py`` must re-export from ``ops`` — the one public path that
  dispatches to both implementations.
"""

from __future__ import annotations

import ast
import dataclasses

from scripts.fedlint.core import Context, Finding, Rule

KERNELS_ROOT = "src/repro/kernels"


@dataclasses.dataclass(frozen=True)
class _Param:
    name: str
    kwonly: bool
    default: str | None  # ast.unparse of the default, or None


def _params(fn: ast.FunctionDef) -> list[_Param]:
    a = fn.args
    out: list[_Param] = []
    pos = list(a.posonlyargs) + list(a.args)
    defaults = [None] * (len(pos) - len(a.defaults)) + [
        ast.unparse(d) for d in a.defaults]
    for arg, d in zip(pos, defaults, strict=True):
        out.append(_Param(arg.arg, False, d))
    for arg, d in zip(a.kwonlyargs, a.kw_defaults, strict=True):
        out.append(_Param(arg.arg, True,
                          ast.unparse(d) if d is not None else None))
    return out


def _public_functions(tree: ast.Module) -> dict[str, ast.FunctionDef]:
    return {
        n.name: n for n in tree.body
        if isinstance(n, ast.FunctionDef) and not n.name.startswith("_")
    }


def _twin_mismatch(ref: list[_Param], twin: list[_Param]) -> str | None:
    """None when ``twin`` can stand in for ``ref``; else why not."""
    ref = [p for p in ref if p.name != "interpret"]
    twin = [p for p in twin if p.name != "interpret"]
    twin_names = [p.name for p in twin]
    positions = []
    for p in ref:
        if p.name not in twin_names:
            return f"missing parameter `{p.name}`"
        positions.append(twin_names.index(p.name))
    if positions != sorted(positions):
        return "shared parameters are in a different order"
    by_name = {p.name: p for p in twin}
    for p in ref:
        q = by_name[p.name]
        if p.default is not None and q.default is not None \
                and p.default != q.default:
            return (f"default for `{p.name}` differs "
                    f"({p.default} vs {q.default})")
    shared = {p.name for p in ref}
    for q in twin:
        if q.name not in shared and not q.kwonly and q.default is None:
            return f"extra required positional parameter `{q.name}`"
    return None


class KernelTwinRule(Rule):
    name = "kernel-twins"
    id_docs = {
        "FED301": "kernel package missing its ops/ref/kernel structure",
        "FED302": "ref oracle without a signature-compatible kernel twin",
        "FED303": "kernel package does not dispatch through ops "
                  "(pallas import, interpret_mode, __init__ re-export)",
    }

    def __init__(self, root_rel: str = KERNELS_ROOT):
        self.root_rel = root_rel

    def finalize(self, ctx: Context) -> list[Finding]:
        root = ctx.root / self.root_rel
        if not root.is_dir() or not ctx.covers(self.root_rel):
            return []
        out: list[Finding] = []
        for pkg in sorted(p for p in root.iterdir() if p.is_dir()):
            if pkg.name.startswith("__"):
                continue
            out.extend(self._check_package(ctx, pkg.name))
        return out

    def _check_package(self, ctx: Context, name: str) -> list[Finding]:
        rel = f"{self.root_rel}/{name}"
        out: list[Finding] = []
        required = ["__init__.py", "ops.py", "ref.py", f"{name}.py"]
        missing = [f for f in required if not ctx.exists(f"{rel}/{f}")]
        if missing:
            return [Finding(rel, 1, "FED301",
                            f"kernel package `{name}` is missing "
                            f"{', '.join(missing)}")]
        ops_src = ctx.source(f"{rel}/ops.py")
        ref_src = ctx.source(f"{rel}/ref.py")
        kern_src = ctx.source(f"{rel}/{name}.py")
        init_src = ctx.source(f"{rel}/__init__.py")

        refs = {n: f for n, f in _public_functions(ref_src.tree).items()
                if n.endswith("_ref")}
        if not refs:
            out.append(Finding(ref_src.rel, 1, "FED301",
                               f"`{name}/ref.py` defines no public `*_ref` "
                               f"oracle function"))
        if not any(
                isinstance(n, ast.Attribute) and n.attr == "pallas_call"
                for n in ast.walk(kern_src.tree)):
            out.append(Finding(kern_src.rel, 1, "FED301",
                               f"`{name}/{name}.py` never invokes "
                               f"`pl.pallas_call`"))

        # FED302: each oracle needs one compatible twin
        candidates = dict(_public_functions(kern_src.tree))
        candidates.update(_public_functions(ops_src.tree))
        for ref_name, ref_fn in sorted(refs.items()):
            ref_sig = _params(ref_fn)
            reasons = []
            for cand_name, cand_fn in sorted(candidates.items()):
                why = _twin_mismatch(ref_sig, _params(cand_fn))
                if why is None:
                    break
                reasons.append(f"{cand_name}: {why}")
            else:
                detail = "; ".join(reasons[:4]) or "no public candidates"
                out.append(Finding(
                    ref_src.rel, ref_fn.lineno, "FED302",
                    f"oracle `{ref_name}` has no signature-compatible twin "
                    f"in {name}/ops.py or {name}/{name}.py ({detail})"))

        # FED303: dispatch plumbing
        kernel_mod = f"repro.kernels.{name}.{name}"
        imports = [n for n in ast.walk(ops_src.tree)
                   if isinstance(n, ast.ImportFrom)]
        if not any((i.module or "") == kernel_mod or
                   (i.level and (i.module or "") == name)
                   for i in imports):
            out.append(Finding(ops_src.rel, 1, "FED303",
                               f"`{name}/ops.py` does not import the kernel "
                               f"module `{kernel_mod}` (no Pallas dispatch)"))
        if not any(isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                   and n.func.id == "interpret_mode"
                   for n in ast.walk(ops_src.tree)):
            out.append(Finding(ops_src.rel, 1, "FED303",
                               f"`{name}/ops.py` never calls "
                               f"`interpret_mode` (no backend dispatch "
                               f"between compiled and interpreted)"))
        ops_mod = f"repro.kernels.{name}.ops"
        init_imports = [n for n in ast.walk(init_src.tree)
                        if isinstance(n, ast.ImportFrom)]
        if not any((i.module or "") == ops_mod or
                   (i.level and (i.module or "") == "ops")
                   for i in init_imports):
            out.append(Finding(init_src.rel, 1, "FED303",
                               f"`{name}/__init__.py` does not re-export "
                               f"from `{ops_mod}`"))
        return out
