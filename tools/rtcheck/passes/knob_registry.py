"""knob-registry: every RT_* knob lives in the rtconfig registry.

The typed `rtconfig` registry is the single source of truth for runtime
knobs: flags are env-overridable (`RT_<NAME>`), overridable per-cluster via
`init(_system_config=...)`, and the resolved table propagates cluster-wide.
An ad-hoc `os.environ.get("RT_*")` read bypasses all three: a knob read
that way is invisible to `_system_config`, undocumented, and unpropagated.

Checks across ray_tpu/ (rtconfig.py itself is exempt — it IS the registry):

- every RT_* env **read** must name either a registered flag's env var
  (flagged as a bypass: use `CONFIG.<flag>`) or a BOOTSTRAP_ALLOWLIST entry
  (process identity / pre-config reads, each with a reason below)
- RT_* env **writes** may only name registered or allowlisted vars (writing
  an unknown var means some child reads it ad hoc)
- any other RT_* string literal must at least be a *known* name — an
  unknown name in an error message or help text is a typo or an
  unregistered knob
- every registered flag must appear (as `RT_<NAME>`) in the README knob
  table — `ray-tpu lint` fails when a new flag lands undocumented
"""

from __future__ import annotations

import ast
import re
from typing import Any

from tools.rtcheck.astutil import dotted
from tools.rtcheck.core import FileCtx, Finding, Pass

_ID = "knob-registry"
_RT_NAME = re.compile(r"^RT_[A-Z0-9_]+$")

REGISTRY_PATH = "ray_tpu/_private/rtconfig.py"
README_PATH = "README.md"

#: Env vars legitimately read straight from os.environ, each because it must
#: exist BEFORE the config snapshot does (or identifies the process itself).
BOOTSTRAP_ALLOWLIST = {
    # Cluster bootstrap: how a client finds the controller at all.
    "RT_ADDRESS": "cluster address, read before any config exists",
    # Read at rpc.py import time so chaos tests can arm injection before
    # the first connection; also a registered flag for _system_config use.
    "RT_FAULT_INJECTION": "armed at import time, before config snapshot",
    # Process identity, set by the node agent when spawning workers.
    "RT_WORKER_ID": "worker process identity (spawn env)",
    "RT_NODE_ID": "worker process identity (spawn env)",
    "RT_SESSION": "worker process identity (spawn env)",
    "RT_CONTROLLER": "worker process identity (spawn env)",
    "RT_AGENT": "worker process identity (spawn env)",
    "RT_HOST": "bind host for multi-machine clusters (bootstrap)",
    "RT_AGENT_STANDALONE": "process-mode marker set by the agent entrypoint",
    "RT_JOB_SUBMISSION_ID": "job-driver identity (spawn env)",
    # Native extension bootstrap: read at import, before rtconfig loads.
    "RT_NATIVE_BUILD_DIR": "native build dir, read at import time",
    "RT_DISABLE_NATIVE": "native kill-switch, read at import time",
    # Topology probe paired with the TPU runtime's own TPU_CHIPS.
    "RT_NUM_TPUS": "accelerator count probe, read before init",
}


class KnobRegistryPass(Pass):
    """RT_* env literals must resolve to registered rtconfig flags."""

    id = _ID

    def wants(self, relpath: str) -> bool:
        return relpath.startswith("ray_tpu/")

    def check_file(self, ctx: FileCtx) -> tuple[list[Finding], Any]:
        facts: dict[str, Any] = {}
        if ctx.path == REGISTRY_PATH:
            flags = _registered_flags(ctx.tree)
            if flags:
                facts["flags"] = flags
            return [], facts or None
        uses = _env_literal_uses(ctx)
        if uses:
            facts["uses"] = uses
        return [], facts or None

    def finalize(self, facts: dict[str, Any], project) -> list[Finding]:
        findings: list[Finding] = []
        flags: dict[str, int] = {}
        for fact in facts.values():
            flags.update(fact.get("flags", {}))
        if not flags:
            if REGISTRY_PATH in project.analyzed:
                findings.append(Finding(
                    _ID, REGISTRY_PATH, 1,
                    "no registered flags found — rtconfig registry parsing "
                    "broke or the registry moved"))
                return findings
            # Restricted-root run (e.g. `rtcheck ray_tpu/serve`): the
            # registry wasn't scanned — read it from disk so the
            # bypass/unregistered checks stay meaningful.
            src = project.read_text(REGISTRY_PATH)
            if src is None:
                return []  # tree without a registry (pass fixtures)
            try:
                flags = _registered_flags(ast.parse(src))
            except SyntaxError:
                return []
            if not flags:
                return []
        env_of = {f"RT_{name.upper()}": name for name in flags}

        for path, fact in sorted(facts.items()):
            for use in fact.get("uses", ()):
                name, line, kind = use["name"], use["line"], use["kind"]
                if name in BOOTSTRAP_ALLOWLIST:
                    continue
                if name in env_of:
                    if kind == "read":
                        findings.append(Finding(
                            _ID, path, line,
                            f"direct env read of {name} bypasses the "
                            f"rtconfig registry (no _system_config "
                            f"override, no cluster propagation) — use "
                            f"`CONFIG.{env_of[name]}`"))
                    continue  # writes/mentions of registered names are fine
                if kind in ("read", "write"):
                    findings.append(Finding(
                        _ID, path, line,
                        f"{name} is not a registered rtconfig flag (and "
                        f"not bootstrap-allowlisted) — add a `_flag(...)` "
                        f"entry and read it via CONFIG"))
                else:
                    findings.append(Finding(
                        _ID, path, line,
                        f"unknown knob name {name} in a string literal — "
                        f"typo, or an unregistered knob being documented"))

        readme = project.read_text(README_PATH) or ""
        for name in sorted(flags):
            env = f"RT_{name.upper()}"
            if env not in readme:
                findings.append(Finding(
                    _ID, REGISTRY_PATH, flags[name],
                    f"registered flag '{name}' ({env}) is missing from the "
                    f"README knob table"))
        return findings


def _registered_flags(tree: ast.AST) -> dict[str, int]:
    """name -> lineno for every `_flag(\"name\", ...)` call in rtconfig."""
    out: dict[str, int] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "_flag" and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            out[node.args[0].value] = node.lineno
    return out


def _env_literal_uses(ctx: FileCtx) -> list[dict]:
    """Every RT_* string literal in the file, classified read/write/mention.

    read:   os.environ.get("RT_X") / os.environ["RT_X"] (Load) /
            os.getenv("RT_X")
    write:  os.environ["RT_X"] = ... / env.setdefault("RT_X", ...) /
            dict-literal keys inside an env-var mapping
    mention: any other literal (docstrings excluded)
    """
    classified: dict[int, str] = {}  # id(Constant node) -> kind

    def _is_environ(node: ast.AST) -> bool:
        d = dotted(node)
        return d is not None and d.split(".")[-1] in ("environ", "env_vars",
                                                      "env")

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and node.args and isinstance(
                    node.args[0], ast.Constant):
                if f.attr in ("get", "pop") and _is_environ(f.value):
                    classified[id(node.args[0])] = "read"
                elif f.attr == "setdefault" and _is_environ(f.value):
                    classified[id(node.args[0])] = "write"
            elif (isinstance(f, ast.Attribute) and f.attr == "getenv"
                  and node.args and isinstance(node.args[0], ast.Constant)):
                classified[id(node.args[0])] = "read"
        elif isinstance(node, ast.Subscript):
            if _is_environ(node.value) and isinstance(node.slice,
                                                      ast.Constant):
                kind = ("write" if isinstance(node.ctx, (ast.Store, ast.Del))
                        else "read")
                classified[id(node.slice)] = kind
        elif isinstance(node, ast.Dict):
            # Dict-literal keys: env mappings built for child processes
            # ({"RT_X": "1"} passed as spawn env / runtime_env env_vars) —
            # some child will READ that var, so it must be a known name.
            for k in node.keys:
                if isinstance(k, ast.Constant):
                    classified.setdefault(id(k), "write")

    # Docstring Constant nodes are documentation, not code.
    doc_ids = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                doc_ids.add(id(body[0].value))

    uses = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and _RT_NAME.match(node.value) and id(node) not in doc_ids):
            if ctx.suppressed(_ID, node.lineno):
                continue
            uses.append({"name": node.value, "line": node.lineno,
                         "kind": classified.get(id(node), "mention")})
    return uses
