"""Shared repo model for drep-lint: one parse of the whole tree.

Every rule runs over the same :class:`RepoModel` — files parsed to ASTs
exactly once, inline waiver comments extracted, module-level constants
and import aliases resolved, and a best-effort intra-repo call graph for
the reachability rules. Pure stdlib (ast + re): the linter must run in
CI images with no JAX backend and lint files it cannot import.

The call graph is deliberately a STATIC under-approximation: it resolves
direct calls (local names, from-imports, ``module.func``), ``self``
method calls (including single-level same-module bases), calls through
class names, and locals assigned from a constructor visible in the same
module. Dynamic dispatch (registries, callbacks, getattr) is not chased
— rules that walk the graph (reader-purity) catch the regression class
that matters (someone adds a direct write to a reader path) and lean on
inline waivers for the intentional remainder.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

WAIVER_RE = re.compile(
    r"#\s*drep-lint:\s*allow\[([A-Za-z0-9_\-, ]+)\]\s*(?:[-—–]+\s*(\S.*))?"
)

# write-capable open() modes: anything that can create or mutate bytes
_WRITE_MODE_CHARS = frozenset("wax+")

# the durable-I/O write funnel's public surface: calls INTO these count
# as writes for the reachability rules (the funnel itself is allowed to
# write; a READER reaching it is the violation)
DURABLE_WRITE_FUNNEL = frozenset({
    "atomic_write", "atomic_write_bytes", "atomic_write_json",
    "atomic_savez", "quarantine_corrupt", "load_npz_or_none",
})

# destructive filesystem calls beyond the payload-write set — relevant
# to reader PURITY (a read-only tool must not mkdir/remove either), too
# noisy/legitimate for the funnel rule (cleanup, scratch dirs)
_DESTRUCTIVE_OS = frozenset({"remove", "unlink", "rmdir", "makedirs", "mkdir"})


@dataclass
class Waiver:
    line: int
    rules: tuple[str, ...]
    reason: str
    path: str = ""
    used: bool = False


@dataclass
class FuncInfo:
    key: str  # "<relpath>::<qualname>"
    path: str
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    # nested function defs visible to Name calls inside this function
    locals_: dict[str, "FuncInfo"] = field(default_factory=dict)


@dataclass
class SourceFile:
    path: str  # repo-relative, posix separators
    module: str  # dotted module name ("drep_tpu.utils.durableio")
    text: str
    tree: ast.Module
    lines: list[str]
    waivers: dict[int, list[Waiver]] = field(default_factory=dict)
    comment_only: set[int] = field(default_factory=set)
    # name -> dotted module ("np" -> "numpy", "telemetry" -> "drep_tpu.utils.telemetry")
    import_aliases: dict[str, str] = field(default_factory=dict)
    # name -> (source module, original name) for `from m import a as b`
    from_imports: dict[str, tuple[str, str]] = field(default_factory=dict)
    functions: dict[str, FuncInfo] = field(default_factory=dict)
    # class name -> {method name -> FuncInfo}
    classes: dict[str, dict[str, FuncInfo]] = field(default_factory=dict)
    class_bases: dict[str, list[str]] = field(default_factory=dict)
    # module-level `NAME = "literal"` string constants
    str_constants: dict[str, str] = field(default_factory=dict)

    def waiver_for(self, rule: str, line: int) -> Waiver | None:
        """A waiver covering `rule` at `line`: same line, or a
        comment-only line immediately above."""
        for cand in (line, line - 1):
            if cand != line and cand not in self.comment_only:
                continue
            for w in self.waivers.get(cand, ()):
                if rule in w.rules:
                    return w
        return None


def _extract_waivers(sf: SourceFile) -> None:
    for i, raw in enumerate(sf.lines, start=1):
        stripped = raw.strip()
        if stripped.startswith("#"):
            sf.comment_only.add(i)
        m = WAIVER_RE.search(raw)
        if m:
            rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
            reason = (m.group(2) or "").strip()
            sf.waivers.setdefault(i, []).append(
                Waiver(line=i, rules=rules, reason=reason, path=sf.path)
            )


def _index_defs(sf: SourceFile) -> None:
    def make(node, qualname: str) -> FuncInfo:
        fi = FuncInfo(
            key=f"{sf.path}::{qualname}", path=sf.path, qualname=qualname,
            node=node,
        )
        for sub in ast.walk(node):
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub is not node
            ):
                fi.locals_[sub.name] = FuncInfo(
                    key=f"{sf.path}::{qualname}.<local>{sub.name}",
                    path=sf.path, qualname=f"{qualname}.<local>{sub.name}",
                    node=sub,
                )
        return fi

    for node in sf.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            sf.functions[node.name] = make(node, node.name)
        elif isinstance(node, ast.ClassDef):
            methods: dict[str, FuncInfo] = {}
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    methods[item.name] = make(item, f"{node.name}.{item.name}")
            sf.classes[node.name] = methods
            sf.class_bases[node.name] = [
                b.id for b in node.bases if isinstance(b, ast.Name)
            ]
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if (
                isinstance(t, ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                sf.str_constants[t.id] = node.value.value


def _index_imports(sf: SourceFile) -> None:
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                sf.import_aliases[alias.asname or alias.name.split(".")[0]] = (
                    alias.name
                )
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for alias in node.names:
                sf.from_imports[alias.asname or alias.name] = (
                    node.module, alias.name
                )


class RepoModel:
    def __init__(self, root: str, paths: list[str] | None = None):
        self.root = os.path.abspath(root)
        self.files: dict[str, SourceFile] = {}
        self.by_module: dict[str, SourceFile] = {}
        self.errors: list[tuple[str, str]] = []  # (path, parse error)
        for rel in sorted(paths if paths is not None else self._discover()):
            loc = os.path.join(self.root, rel)
            try:
                with open(loc, encoding="utf-8") as f:
                    text = f.read()
                tree = ast.parse(text, filename=rel)
            except (OSError, SyntaxError, ValueError) as e:
                self.errors.append((rel, str(e)))
                continue
            module = rel[:-3].replace("/", ".")
            if module.endswith(".__init__"):
                module = module[: -len(".__init__")]
            sf = SourceFile(
                path=rel, module=module, text=text, tree=tree,
                lines=text.splitlines(),
            )
            _extract_waivers(sf)
            _index_defs(sf)
            _index_imports(sf)
            self.files[rel] = sf
            self.by_module[module] = sf

    def _discover(self) -> list[str]:
        rels: list[str] = []
        for top in ("drep_tpu", "tools", "tests"):
            base = os.path.join(self.root, top)
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [
                    d for d in dirnames if d != "__pycache__"
                ]
                rel_dir = os.path.relpath(dirpath, self.root).replace(os.sep, "/")
                if rel_dir == "tools/lint" or rel_dir.startswith("tools/lint/"):
                    continue  # the linter does not lint itself
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        rels.append(f"{rel_dir}/{fn}")
        for top_file in ("__graft_entry__.py", "chip_smoke.py"):
            if os.path.exists(os.path.join(self.root, top_file)):
                rels.append(top_file)
        return rels

    # -- scopes -------------------------------------------------------------

    def prod_files(self):
        """The production scope: pipeline + tools, never tests."""
        for sf in self.files.values():
            if not sf.path.startswith("tests/"):
                yield sf

    def test_files(self):
        for sf in self.files.values():
            if sf.path.startswith("tests/"):
                yield sf

    # -- call resolution ----------------------------------------------------

    def resolve_module(self, sf: SourceFile, name: str) -> SourceFile | None:
        """The repo SourceFile a local alias refers to, if intra-repo."""
        dotted = sf.import_aliases.get(name)
        if dotted is None and name in sf.from_imports:
            mod, orig = sf.from_imports[name]
            dotted = f"{mod}.{orig}"  # `from drep_tpu.utils import faults`
        if dotted is None:
            return None
        return self.by_module.get(dotted)

    def _class_method(
        self, sf: SourceFile, cls: str, meth: str
    ) -> FuncInfo | None:
        seen: set[str] = set()
        stack = [cls]
        while stack:
            c = stack.pop()
            if c in seen:
                continue
            seen.add(c)
            fi = sf.classes.get(c, {}).get(meth)
            if fi is not None:
                return fi
            stack.extend(sf.class_bases.get(c, ()))
        return None

    def resolve_call(
        self, call: ast.Call, sf: SourceFile, ctx: FuncInfo | None
    ) -> list[FuncInfo]:
        """Best-effort static targets of a call, intra-repo only."""
        fn = call.func
        out: list[FuncInfo] = []
        if isinstance(fn, ast.Name):
            name = fn.id
            if ctx is not None and name in ctx.locals_:
                return [ctx.locals_[name]]
            if name in sf.functions:
                return [sf.functions[name]]
            if name in sf.from_imports:
                mod, orig = sf.from_imports[name]
                target = self.by_module.get(mod)
                if target is not None and orig in target.functions:
                    return [target.functions[orig]]
                if target is not None and orig in target.classes:
                    init = self._class_method(target, orig, "__init__")
                    return [init] if init is not None else []
            if name in sf.classes:
                init = self._class_method(sf, name, "__init__")
                return [init] if init is not None else []
            return out
        if not isinstance(fn, ast.Attribute):
            return out
        base = fn.value
        if isinstance(base, ast.Name):
            if base.id == "self" and ctx is not None and "." in ctx.qualname:
                cls = ctx.qualname.split(".")[0]
                fi = self._class_method(sf, cls, fn.attr)
                return [fi] if fi is not None else []
            target = self.resolve_module(sf, base.id)
            if target is not None:
                if fn.attr in target.functions:
                    return [target.functions[fn.attr]]
                return out
            # ClassName.method, or a from-imported class
            if base.id in sf.classes:
                fi = self._class_method(sf, base.id, fn.attr)
                return [fi] if fi is not None else []
            if base.id in sf.from_imports:
                mod, orig = sf.from_imports[base.id]
                tmod = self.by_module.get(mod)
                if tmod is not None and orig in tmod.classes:
                    fi = self._class_method(tmod, orig, fn.attr)
                    return [fi] if fi is not None else []
            # local assigned from a visible constructor: x = Foo(...); x.m()
            if ctx is not None:
                cls_file, cls_name = _infer_local_class(self, sf, ctx, base.id)
                if cls_name is not None:
                    fi = self._class_method(cls_file, cls_name, fn.attr)
                    return [fi] if fi is not None else []
        return out


def _infer_local_class(
    model: RepoModel, sf: SourceFile, ctx: FuncInfo, var: str
):
    """`x = ClassName(...)` in the same function -> (file, ClassName)."""
    for node in ast.walk(ctx.node):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        t = node.targets[0]
        if not (isinstance(t, ast.Name) and t.id == var):
            continue
        v = node.value
        if isinstance(v, ast.Call) and isinstance(v.func, ast.Name):
            name = v.func.id
            if name in sf.classes:
                return sf, name
            if name in sf.from_imports:
                mod, orig = sf.from_imports[name]
                tmod = model.by_module.get(mod)
                if tmod is not None and orig in tmod.classes:
                    return tmod, orig
    return sf, None


# -- write-capable call detection (shared by durable-funnel + reader-purity) -


def _mode_shaped(v) -> bool:
    """Looks like an open() mode, not a path/member name that happens to
    contain 'w' (zf.open("data.txt") binds arg 0 to a NAME)."""
    return (
        isinstance(v, str) and 0 < len(v) <= 3
        and all(c in "rwaxbt+U" for c in v)
    )


def _open_mode(call: ast.Call, mode_pos: int) -> str | None:
    """The literal mode of an open() call; `mode_pos` is the positional
    index of the mode argument — 1 for builtin open(path, mode), 0 for
    the method spelling p.open(mode) (pathlib binds the path as self)."""
    if len(call.args) > mode_pos and isinstance(call.args[mode_pos], ast.Constant):
        v = call.args[mode_pos].value
        return v if _mode_shaped(v) else None
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            v = kw.value.value
            return v if _mode_shaped(v) else None
    if len(call.args) > mode_pos or any(kw.arg == "mode" for kw in call.keywords):
        return None  # non-literal mode: undecidable, out of static reach
    return "r"


def write_call_kind(call: ast.Call) -> str | None:
    """Label of a durable-payload-writing call, or None. The set is the
    contract's (ISSUE 12): open in w/a/x/+ modes, np.save/np.savez*,
    json.dump/pickle.dump, os.rename/os.replace, Path.write_*."""
    fn = call.func
    if isinstance(fn, ast.Name) and fn.id == "open" or (
        isinstance(fn, ast.Attribute) and fn.attr == "open"
    ):
        mode = _open_mode(call, 1 if isinstance(fn, ast.Name) else 0)
        if mode is not None and any(c in _WRITE_MODE_CHARS for c in mode):
            return f'open(mode="{mode}")'
        return None
    if not isinstance(fn, ast.Attribute):
        return None
    base = fn.value
    base_name = base.id if isinstance(base, ast.Name) else None
    if fn.attr in ("savez", "savez_compressed", "save") and base_name in (
        "np", "numpy"
    ):
        return f"np.{fn.attr}"
    if fn.attr == "dump" and base_name in ("json", "pickle"):
        return f"{base_name}.dump"
    if fn.attr in ("rename", "replace") and base_name == "os":
        return f"os.{fn.attr}"
    if fn.attr in ("write_text", "write_bytes"):
        return f"Path.{fn.attr}"
    return None


def destructive_call_kind(call: ast.Call) -> str | None:
    """Filesystem mutations beyond payload writes (reader-purity only)."""
    fn = call.func
    if not isinstance(fn, ast.Attribute):
        return None
    base_name = fn.value.id if isinstance(fn.value, ast.Name) else None
    if base_name == "os" and fn.attr in _DESTRUCTIVE_OS:
        return f"os.{fn.attr}"
    if base_name == "shutil" and fn.attr in ("rmtree", "move", "copy", "copy2"):
        return f"shutil.{fn.attr}"
    if base_name not in ("os", "shutil") and fn.attr in ("unlink", "rmdir"):
        return f".{fn.attr}() (Path)"
    return None


def funnel_call_name(call: ast.Call) -> str | None:
    """A call into the durable-write funnel's public API, by name."""
    fn = call.func
    name = None
    if isinstance(fn, ast.Name):
        name = fn.id
    elif isinstance(fn, ast.Attribute):
        name = fn.attr
    return name if name in DURABLE_WRITE_FUNNEL else None


def iter_calls(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            yield sub
