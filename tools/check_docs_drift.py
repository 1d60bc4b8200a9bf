#!/usr/bin/env python
"""Fail when the prose drifts from the code it describes.

Checks, over README.md and docs/*.md:

  * every repo file path referenced in backticks or markdown links exists
    (``src/repro/core/runtime.py``, ``docs/BENCHMARKS.md``, ...), including
    dotted module spellings (``repro.launch.serve`` -> src/repro/launch/
    serve.py) and ``path.py: member`` / ``module.attr`` suffixes;
  * every ``--flag`` the docs mention is actually defined by some
    ``add_argument`` call in src/, benchmarks/, or tools/;
  * every backend key in ``STORE_BACKENDS`` is mentioned in README.md and
    docs/ARCHITECTURE.md (a new backend must be documented; a renamed one
    fails the path/flag checks on the stale side);
  * the serving-config surface stays honest both ways: every deployment
    profile in ``repro.config.PROFILES`` is documented in README.md AND
    docs/ARCHITECTURE.md, every ``profile:<name>`` / ``SWAPNET_*`` token
    the docs mention exists in code, every documented dotted config key
    (``runtime.budget_mb`` style) is a real ``ServeConfig`` field, the
    HTTP endpoint tables match ``repro.serving.control_plane.ENDPOINTS``
    exactly (both directions: undocumented endpoint = drift, documented
    ghost endpoint = drift), and the ``/metrics`` family table matches
    ``repro.serving.metrics.METRIC_FAMILIES`` (both directions).

Docs rot silently: a rename refactor updates every import but no grep hits
the prose. This runs in CI next to the test suite so the rename PR is the
one that fixes its own docs. Heuristic by design — only tokens that LOOK
like repo paths or flags are validated; plain prose is never parsed.

One non-docs hygiene check rides along: every ``results/*.json`` path that
``benchmarks/check_regression.py`` or ``.github/workflows/ci.yml``
references must be git-TRACKED. ``.gitignore`` ignores results scratch
patterns, so a new baseline/fixture file that matches one (or a rename
that forgets ``git add``) would otherwise sit untracked forever while CI
quietly gates against a stale committed copy.
"""
from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DOC_FILES = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]
CODE_DIRS = ["src", "benchmarks", "tools", "tests"]

# prefixes a backticked token must start with to be treated as a repo path
PATH_PREFIXES = ("src/", "benchmarks/", "tests/", "docs/", "results/",
                 "tools/", ".github/", "repro.", "benchmarks.")
# flags owned by external tools the docs may legitimately mention
EXTERNAL_FLAGS = {"--smoke-test"}  # (none currently; keep the hook)


def backtick_tokens(text: str) -> list[str]:
    # inline code spans + fenced code blocks, then link targets
    toks = re.findall(r"`([^`\n]+)`", text)
    for block in re.findall(r"```[a-z]*\n(.*?)```", text, re.S):
        toks.extend(block.split())
    toks.extend(re.findall(r"\]\(([^)#\s]+)\)", text))
    return toks


def defined_flags() -> set[str]:
    flags: set[str] = set()
    for d in CODE_DIRS:
        for py in (ROOT / d).rglob("*.py"):
            try:
                tree = ast.parse(py.read_text())
            except SyntaxError:
                continue
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "add_argument"):
                    for a in node.args:
                        if (isinstance(a, ast.Constant)
                                and isinstance(a.value, str)
                                and a.value.startswith("--")):
                            flags.add(a.value)
    return flags


def resolve_path(tok: str) -> bool:
    """True if ``tok`` names something real in the repo."""
    tok = tok.strip().rstrip(".,;:")
    if tok.startswith(("repro.", "benchmarks.")):  # python -m spelling
        mod = tok.split()[0]
        rel = mod.replace(".", "/")
        base = "src/" if mod.startswith("repro.") else ""
        return ((ROOT / f"{base}{rel}.py").exists()
                or (ROOT / base / rel).is_dir())
    candidates = [tok, tok.split(":")[0].strip()]
    # `store/base.py` style (relative to a dir named in the section) and
    # `kernels/swap_linear.vmem_bytes` style (module.attr) both reduce to:
    # strip a trailing .member if the base resolves
    if "." in tok.rsplit("/", 1)[-1]:
        stem = tok[:tok.rfind(".")]
        candidates += [stem, stem + ".py"]
    for c in candidates:
        c = c.strip().rstrip(".,;:")
        if not c:
            continue
        if (ROOT / c).exists():
            return True
        # paths quoted relative to src/repro/ inside module-map sections
        if (ROOT / "src" / "repro" / c).exists():
            return True
    return False


def looks_like_path(tok: str) -> bool:
    if " " in tok and not tok.startswith(("repro.", "benchmarks.")):
        return False
    if any(ch in tok for ch in "{}*|\\()<>="):
        return False
    return tok.startswith(PATH_PREFIXES) or (
        "/" in tok and tok.rsplit("/", 1)[-1].count(".") >= 1
        and not tok.startswith(("http", "0.", "1.")))


def check_serving_config(readme: str, arch: str) -> list[str]:
    """Profiles, config keys, env vars, HTTP endpoints and metric
    families: docs <-> code, both directions."""
    from repro.config import ENV_PREFIX, config_fields, profile_names
    from repro.serving.control_plane import ENDPOINTS
    errors: list[str] = []
    fields = config_fields()
    known_profiles = set(profile_names())
    env_map = {ENV_PREFIX + path.replace(".", "_").upper()
               for path in fields}
    env_map.add(ENV_PREFIX + "PROFILE")

    for name, text in [("README.md", readme), ("docs/ARCHITECTURE.md", arch)]:
        # every shipped profile must be documented...
        for prof in sorted(known_profiles):
            if not re.search(rf"`{re.escape(prof)}`", text):
                errors.append(f"{name}: deployment profile `{prof}` "
                              f"(repro.config.PROFILES) is undocumented")
        toks = backtick_tokens(text)
        for tok in toks:
            tok = tok.strip().rstrip(".,;:")
            # ...and every documented profile/env/config token must exist
            m = re.match(r"^profile:([\w-]+)$", tok)
            if m and m.group(1) not in known_profiles:
                errors.append(f"{name}: unknown profile `{m.group(1)}`")
            for var in re.findall(rf"\b({ENV_PREFIX}[A-Z0-9_]+)\b", tok):
                if "<" in tok:          # template spellings like
                    continue            # SWAPNET_<SECTION>_<KEY>
                if var not in env_map:
                    errors.append(f"{name}: env var `{var}` is not a "
                                  f"ServeConfig field")
            m = re.match(r"^(workload|runtime|scheduler|http)\.(\w+)$", tok)
            if m and m.group(2) != "py" and tok not in fields:
                # (module-map lines like `runtime.py` are paths, not keys)
                errors.append(f"{name}: config key `{tok}` is not a "
                              f"ServeConfig field")

    # metric families: every `swapnet_*` name the docs use is exported,
    # and the ARCHITECTURE.md table lists exactly the exported families
    from repro.serving.metrics import METRIC_FAMILIES
    families = {name for name, _, _ in METRIC_FAMILIES}
    for name, text in [("README.md", readme), ("docs/ARCHITECTURE.md", arch)]:
        for fam in sorted(set(re.findall(r"\bswapnet_[a-z_]+\b", text))
                          - families):
            errors.append(f"{name}: metric `{fam}` is not in "
                          f"serving.metrics.METRIC_FAMILIES")
    table = set(re.findall(r"^\| `(swapnet_[a-z_]+)` \|", arch, re.M))
    for fam in sorted(families - table):
        errors.append(f"docs/ARCHITECTURE.md: metric family `{fam}` "
                      f"(METRIC_FAMILIES) is missing from the /metrics table")

    # endpoint tables: exact two-way match against the code's contract
    code_eps = {(meth, path) for meth, path in ENDPOINTS}
    for name, text in [("README.md", readme), ("docs/ARCHITECTURE.md", arch)]:
        doc_eps = set()
        for meth, path in re.findall(
                r"(GET|POST)\W+`(/[\w/<>.-]*)`", text):
            doc_eps.add((meth, path))
        for meth, path in re.findall(            # README prose spelling:
                r"`(GET|POST) (/[\w/<>.-]*)`", text):   # `GET /healthz`
            doc_eps.add((meth, path))
        if not doc_eps:
            errors.append(f"{name}: no HTTP endpoint reference found "
                          f"(expected the control-plane endpoints)")
            continue
        for ep in sorted(code_eps - doc_eps):
            errors.append(f"{name}: endpoint {ep[0]} {ep[1]} "
                          f"(control_plane.ENDPOINTS) is undocumented")
        for ep in sorted(doc_eps - code_eps):
            errors.append(f"{name}: documents endpoint {ep[0]} {ep[1]} "
                          f"which the control plane does not serve")
    return errors


def check_tracked_results() -> list[str]:
    """Every results/*.json path referenced by the regression gate or the
    CI workflow must be tracked in git. Skips silently when git (or the
    .git dir) is unavailable — a source tarball can still run the docs
    checks."""
    import subprocess
    refs: set[str] = set()
    for src in (ROOT / "benchmarks" / "check_regression.py",
                ROOT / ".github" / "workflows" / "ci.yml"):
        if src.exists():
            refs.update(re.findall(r"results/[\w.-]+\.json",
                                   src.read_text()))
    # files CI (re)generates fresh on every run are artifacts, not
    # fixtures — only the committed baseline inputs must be tracked,
    # and those are exactly the paths the gate READS as its baseline
    # plus any fixture the reporting tests pin (all BENCH_*.json today)
    try:
        out = subprocess.run(
            ["git", "ls-files", "--", "results/"], cwd=ROOT,
            capture_output=True, text=True, timeout=30, check=True).stdout
    except (OSError, subprocess.SubprocessError):
        return []
    tracked = set(out.split())
    return [f"results hygiene: `{p}` is referenced by the CI gate but not "
            f"git-tracked (matched a .gitignore scratch pattern, or "
            f"`git add` was forgotten)"
            for p in sorted(refs) if p not in tracked]


def main() -> int:
    flags = defined_flags()
    errors: list[str] = []
    readme = (ROOT / "README.md").read_text()
    arch = (ROOT / "docs" / "ARCHITECTURE.md").read_text()

    for doc in DOC_FILES:
        text = doc.read_text()
        rel = doc.relative_to(ROOT)
        for tok in backtick_tokens(text):
            tok = tok.strip()
            if looks_like_path(tok) and not resolve_path(tok):
                errors.append(f"{rel}: stale path reference `{tok}`")
            for flag in re.findall(r"(?<![\w-])(--[a-z][a-z0-9-]+)", tok):
                if flag not in flags and flag not in EXTERNAL_FLAGS:
                    errors.append(f"{rel}: flag `{flag}` is not defined by "
                                  f"any add_argument in {CODE_DIRS}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.store import STORE_BACKENDS
    for backend in STORE_BACKENDS:
        for name, text in [("README.md", readme),
                           ("docs/ARCHITECTURE.md", arch)]:
            if not re.search(rf"`{backend}`", text):
                errors.append(f"{name}: store backend `{backend}` "
                              f"(STORE_BACKENDS) is undocumented")

    errors += check_serving_config(readme, arch)
    errors += check_tracked_results()

    if errors:
        print(f"docs drift: {len(errors)} problem(s)")
        for e in sorted(set(errors)):
            print(f"  {e}")
        return 1
    n = sum(len(backtick_tokens(d.read_text())) for d in DOC_FILES)
    print(f"docs drift: OK ({len(DOC_FILES)} docs, {n} code tokens, "
          f"{len(flags)} known flags, {len(STORE_BACKENDS)} backends)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
