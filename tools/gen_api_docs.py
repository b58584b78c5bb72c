"""Generate docs/API.md from the public docstrings (DESIGN.md cross-refs
included) — the reference is derived from source, never hand-maintained.

    PYTHONPATH=src python tools/gen_api_docs.py            # (re)write docs/API.md
    PYTHONPATH=src python tools/gen_api_docs.py --check    # exit 1 on drift (CI)

Every public function/class whose docstring names a ``DESIGN.md §N``
section is linked to it; the tool also prints a coverage summary so the
docs CI job can flag public API that lost its design cross-reference.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

# module path -> one-line role in the system (order = document order)
MODULES = [
    ("repro.core.spec", "on-disk constants, flags, header geometry"),
    ("repro.core.header", "header encode/decode"),
    ("repro.core.dtypes", "eltype <-> numpy dtype mapping"),
    ("repro.core.io", "read / write / memmap / streaming RaWriter"),
    ("repro.core.quant", "typed quantized-field metadata schema"),
    ("repro.core.engine", "parallel chunked I/O engine"),
    ("repro.core.codec", "chunked compression codec"),
    ("repro.core.stats", "per-chunk statistics + predicate pushdown"),
    ("repro.core.layouts", "single-source-of-truth on-disk layout registry"),
    ("repro.core.obs", "in-program spans and counters on the device trace's clock"),
    ("repro.core.sharded", "sharded stores (read + streaming write)"),
    ("repro.core.racat", "CLI introspection / verify / compress / ingest"),
    ("repro.remote.server", "HTTP byte-range + upload server"),
    ("repro.remote.client", "parallel-range reader, RemoteWriter, uploads"),
    ("repro.remote.cache", "block-aligned LRU cache"),
    ("repro.data.dataset", "dataset directories: RaDataset, DatasetBuilder"),
    ("repro.data.loader", "training DataLoader"),
    ("repro.data.device_loader", "prefetch-to-device feed + on-device dequant"),
    ("repro.data.synth", "synthetic dataset builders"),
    ("repro.distributed.data_mesh", "shard-ownership data mesh (elastic multi-host ingest)"),
    ("repro.checkpoint.store", "checkpoint save/restore (local + URL)"),
    ("repro.fleet.router", "consistent-hash router/proxy over replicas"),
    ("repro.fleet.edge", "read-through edge cache (RAM/disk/origin)"),
    ("repro.fleet.loadgen", "async trace-replay load generator"),
    ("repro.formats.ingest", "foreign-format -> dataset converters"),
    ("repro.formats.npy", ".npy baseline"),
    ("repro.formats.hdf5min", "minimal HDF5 baseline"),
    ("repro.formats.png", "PNG codec baseline"),
    ("repro.formats.nrrd", "NRRD baseline"),
    ("repro.devtools.lint", "ralint: codebase-invariant AST linter"),
    ("repro.devtools.tsan", "runtime concurrency sanitizer (locks + guarded fields)"),
    ("repro.devtools.doctor", "racat doctor: file geometry vs the layout registry"),
]

SECTION_RE = re.compile(r"DESIGN\.md (§\d+)")


def first_paragraph(doc: str | None) -> str:
    if not doc:
        return ""
    para = inspect.cleandoc(doc).split("\n\n", 1)[0]
    return " ".join(line.strip() for line in para.splitlines())


def design_refs(doc: str | None) -> list[str]:
    return sorted(set(SECTION_RE.findall(doc or "")))


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def _public_members(mod):
    fns, classes = [], []
    for name, obj in vars(mod).items():
        if name.startswith("_") or getattr(obj, "__module__", None) != mod.__name__:
            continue
        if inspect.isfunction(obj):
            fns.append((name, obj))
        elif inspect.isclass(obj):
            classes.append((name, obj))
    return fns, classes


def _render_callable(name: str, obj, *, prefix: str = "", level: str = "####") -> list[str]:
    out = [f"{level} `{prefix}{name}{_signature(obj)}`", ""]
    para = first_paragraph(obj.__doc__)
    refs = design_refs(obj.__doc__)
    if para:
        out += [para, ""]
    if refs:
        out += ["*Design:* " + ", ".join(f"DESIGN.md {r}" for r in refs), ""]
    return out


def render(missing: list) -> str:
    lines = [
        "# API reference",
        "",
        "Generated from docstrings by `tools/gen_api_docs.py` — do not edit",
        "by hand (CI fails on drift; regenerate with"
        " `PYTHONPATH=src python tools/gen_api_docs.py`).",
        "Section pointers (`DESIGN.md §N`) link each entry to the design",
        "document that specifies its wire behavior.",
        "",
    ]
    for modname, role in MODULES:
        mod = importlib.import_module(modname)
        lines += [f"## `{modname}` — {role}", ""]
        para = first_paragraph(mod.__doc__)
        if para:
            lines += [para, ""]
        refs = design_refs(mod.__doc__)
        if refs:
            lines += ["*Design:* " + ", ".join(f"DESIGN.md {r}" for r in refs), ""]
        fns, classes = _public_members(mod)
        for name, obj in sorted(fns):
            lines += _render_callable(name, obj, level="####")
            if not design_refs(obj.__doc__) and not design_refs(mod.__doc__):
                missing.append(f"{modname}.{name}")
        for name, cls in sorted(classes):
            lines += [f"### class `{name}`", ""]
            para = first_paragraph(cls.__doc__)
            if para:
                lines += [para, ""]
            crefs = design_refs(cls.__doc__)
            if crefs:
                lines += ["*Design:* " + ", ".join(f"DESIGN.md {r}" for r in crefs), ""]
            elif not design_refs(mod.__doc__):
                missing.append(f"{modname}.{name}")
            for mname, m in sorted(vars(cls).items()):
                if mname.startswith("_") or not (
                    inspect.isfunction(m) or isinstance(m, (classmethod, staticmethod, property))
                ):
                    continue
                target = m.fget if isinstance(m, property) else (
                    m.__func__ if isinstance(m, (classmethod, staticmethod)) else m
                )
                if isinstance(m, property):
                    lines += [f"#### `{name}.{mname}` *(property)*", ""]
                    p = first_paragraph(target.__doc__)
                    if p:
                        lines += [p, ""]
                else:
                    lines += _render_callable(mname, target, prefix=f"{name}.")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) if docs/API.md is stale")
    args = ap.parse_args(argv)
    missing: list = []
    text = render(missing)
    out = os.path.join(REPO, "docs", "API.md")
    if missing:
        print(f"note: {len(missing)} public symbols lack a DESIGN.md §N "
              f"cross-reference (module- or symbol-level):", file=sys.stderr)
        for m in missing:
            print(f"  - {m}", file=sys.stderr)
    if args.check:
        try:
            with open(out) as f:
                current = f.read()
        except FileNotFoundError:
            current = ""
        if current != text:
            print(f"FAIL: {out} is stale; regenerate with "
                  f"`PYTHONPATH=src python tools/gen_api_docs.py`", file=sys.stderr)
            return 1
        print(f"OK: {out} matches the source docstrings")
        return 0
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(text)
    print(f"wrote {out} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
