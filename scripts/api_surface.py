"""Public-API surface dump + CI drift gate (ISSUE 5).

Walks the public modules and writes every exported name with its
signature to ``api_surface.txt`` at the repo root — the committed record
of the facade + stable-internal-layer contract.  ``--check`` regenerates
the text in memory and fails (exit 1, unified diff on stdout) when the
code's surface no longer matches the committed file, so API changes are
always a *declared* part of a PR (regenerate with
``python scripts/api_surface.py``), never an accident.

    PYTHONPATH=src python scripts/api_surface.py          # (re)write
    PYTHONPATH=src python scripts/api_surface.py --check  # CI gate
"""

import argparse
import difflib
import importlib
import inspect
import os
import sys

# The declared public surface: the facade, the stable internal layer it
# dispatches to, and the tooling seams.  Order is the file's section order.
MODULES = [
    "repro",
    "repro.frame",
    "repro.core",
    "repro.core.schema",
    "repro.core.table",
    "repro.core.snapshot",
    "repro.core.hashindex",
    "repro.core.hashing",
    "repro.core.joins",
    "repro.core.planner",
    "repro.dist",
    "repro.dist.dtable",
    "repro.dist.mesh",
    "repro.dist.shuffle",
    "repro.dist.checkpoint",
    "repro.dist.runtime",
    "repro.dist.resilience",
    "repro.serving",
    "repro.serving.query_engine",
    "repro.obs",
]

OUT_NAME = "api_surface.txt"


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return sorted(
        n for n, v in vars(mod).items()
        if not n.startswith("_") and not inspect.ismodule(v)
        and getattr(v, "__module__", mod.__name__) == mod.__name__)


def _class_lines(qual: str, cls) -> list:
    lines = [f"{qual}{_signature(cls)}"]
    members = []
    for name, member in sorted(vars(cls).items()):
        if name.startswith("_"):
            continue
        if isinstance(member, property):
            members.append(f"  .{name} [property]")
        elif isinstance(member, (classmethod, staticmethod)):
            members.append(f"  .{name}{_signature(member.__func__)}")
        elif inspect.isfunction(member):
            members.append(f"  .{name}{_signature(member)}")
    return lines + members


def render() -> str:
    lines = [
        "# Public API surface — generated by scripts/api_surface.py;",
        "# regenerate after any intentional surface change",
        "# (scripts/ci.sh fails on drift).",
    ]
    for modname in MODULES:
        mod = importlib.import_module(modname)
        lines.append("")
        lines.append(f"== {modname} ==")
        for name in _public_names(mod):
            obj = getattr(mod, name)
            qual = f"{modname}.{name}"
            if inspect.isclass(obj):
                lines.extend(_class_lines(qual, obj))
            elif callable(obj):
                lines.append(f"{qual}{_signature(obj)}")
            elif inspect.ismodule(obj):
                lines.append(f"{qual} [module]")
            else:
                lines.append(f"{qual} = {obj!r}")
    return "\n".join(lines) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="fail if the committed surface file is stale")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, OUT_NAME)
    text = render()
    if not args.check:
        with open(path, "w") as f:
            f.write(text)
        print(f"wrote {path} ({text.count(chr(10))} lines)")
        return 0
    with open(path) as f:
        committed = f.read()
    if committed == text:
        print("API SURFACE OK")
        return 0
    sys.stdout.writelines(difflib.unified_diff(
        committed.splitlines(keepends=True), text.splitlines(keepends=True),
        fromfile=f"{OUT_NAME} (committed)", tofile=f"{OUT_NAME} (code)"))
    print("\nAPI SURFACE DRIFT: the public API changed without "
          "regenerating api_surface.txt\n  -> PYTHONPATH=src python "
          "scripts/api_surface.py   (then commit the diff)")
    return 1


if __name__ == "__main__":
    sys.exit(main())
