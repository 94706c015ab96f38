"""Registry-derived documentation tables — docs that cannot drift.

Generates markdown tables straight from the live registries and splices
them into marker blocks inside checked-in markdown files:

* ``registry-tables`` (README.md) — every registered op (with its
  backends) and every registered pass
  (:func:`repro.core.registered_ops` / :func:`repro.core.registered_passes`);
* ``serving-ops`` (docs/architecture.md §6) — the serving hot-path ops:
  one row per (op, backend) with the backend's ``supports()`` constraint
  and cost-model provenance, pulled from the
  :class:`repro.core.registry.OpImpl` metadata.

Marker blocks look like::

    <!-- BEGIN GENERATED: registry-tables -->
    ...regenerated content...
    <!-- END GENERATED: registry-tables -->

Every marker pair found in a file is regenerated; unknown block names are
an error (a typo'd marker would otherwise rot silently).

Usage::

    python -m repro.tools.docgen                           # print all tables
    python -m repro.tools.docgen --update README.md --update docs/architecture.md
    python -m repro.tools.docgen --check README.md --check docs/architecture.md

CI runs ``--check`` on both files, so a new op/pass/backend (or an edited
``supports()`` constraint) that isn't re-generated into the docs fails
the build.
"""

from __future__ import annotations

import argparse
import re
import sys

__all__ = ["ops_table", "passes_table", "serving_ops_table",
           "splice", "main"]

_MARKER_RE = re.compile(
    r"<!-- BEGIN GENERATED: ([\w-]+) -->.*?<!-- END GENERATED: \1 -->",
    re.DOTALL)

# ops on the serving hot path (the engine's prefill/decode Programs plus
# the speculative draft/verify Programs), dense and paged — the §6
# reference table documents exactly these
SERVING_OPS = ("embedding", "cache_update", "chunk_attention",
               "decode_attention", "greedy_token",
               "paged_cache_update",
               "paged_chunk_attention", "paged_decode_attention",
               "paged_cache_update_q", "paged_chunk_attention_q",
               "paged_decode_attention_q", "last_new_row")


def _first_line(text: str) -> str:
    for line in (text or "").strip().splitlines():
        line = line.strip()
        if line:
            return line.replace("|", "\\|")  # keep markdown table cells intact
    return ""


def _one_line(text: str) -> str:
    """Whole docstring collapsed to one markdown-safe line (supports() and
    cost_fn docstrings wrap; truncating at the first physical line would
    ship cells cut mid-sentence)."""
    return " ".join((text or "").split()).replace("|", "\\|")


def ops_table() -> str:
    """Markdown table of every registered op: backends + one-line doc."""
    from repro.core import get_op, registered_ops
    rows = ["| op | backends | doc |", "|---|---|---|"]
    for name in registered_ops():
        op = get_op(name)
        backends = ", ".join(
            f"`{b}`" for b in sorted(op.impls, key=lambda b: (b != "ref", b)))
        rows.append(f"| `{name}` | {backends} | {_first_line(op.doc)} |")
    return "\n".join(rows)


def passes_table() -> str:
    """Markdown table of every registered pass + first docstring line."""
    from repro.core import get_pass, registered_passes
    rows = ["| pass | summary |", "|---|---|"]
    for name in registered_passes():
        if name.startswith("_"):
            continue  # test-registered scratch passes
        rows.append(f"| `{name}` | {_first_line(get_pass(name).__doc__)} |")
    return "\n".join(rows)


def serving_ops_table() -> str:
    """Markdown reference of the serving ops: one row per (op, backend)
    with the ``supports()`` constraint (the guard function's docstring —
    '(none)' for unconditional backends) and the cost model in effect
    (per-impl override docstring, or the op-level default)."""
    from repro.core import get_op
    rows = ["| op | backend | supports() constraint | cost model | note |",
            "|---|---|---|---|---|"]
    for name in SERVING_OPS:
        op = get_op(name)
        for backend in sorted(op.impls, key=lambda b: (b != "ref", b)):
            im = op.impls[backend]
            guard = _one_line(getattr(im.supports, "__doc__", "")) or "(none)"
            cost = (_one_line(getattr(im.cost_fn, "__doc__", ""))
                    if im.cost_fn is not None else "op default")
            rows.append(f"| `{name}` | `{backend}` | {guard} | {cost} | "
                        f"{_one_line(im.note) or '-'} |")
    return "\n".join(rows)


def _block(name: str) -> str:
    import repro  # noqa: F401  (registers all ops, passes and backends)
    if name == "registry-tables":
        body = (f"### Registered passes\n\n{passes_table()}\n\n"
                f"### Registered ops\n\n{ops_table()}")
    elif name == "serving-ops":
        body = (f"### Serving ops & backends (generated)\n\n"
                f"{serving_ops_table()}")
    else:
        raise SystemExit(f"unknown generated block {name!r}; "
                         f"known: registry-tables, serving-ops")
    return (f"<!-- BEGIN GENERATED: {name} -->\n{body}\n"
            f"<!-- END GENERATED: {name} -->")


def splice(text: str) -> str:
    """Regenerate every marker block found in ``text``."""
    if not _MARKER_RE.search(text):
        raise SystemExit(
            "no marker block found; add\n"
            "<!-- BEGIN GENERATED: <name> -->\n<!-- END GENERATED: <name> -->\n"
            "to the file first")
    return _MARKER_RE.sub(lambda m: _block(m.group(1)), text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", metavar="FILE", action="append", default=[],
                    help="rewrite marker blocks in FILE (repeatable)")
    ap.add_argument("--check", metavar="FILE", action="append", default=[],
                    help="exit 1 when FILE's marker blocks are stale "
                         "(repeatable)")
    args = ap.parse_args(argv)
    stale = 0
    for path in args.update:
        with open(path) as f:
            text = f.read()
        new = splice(text)
        if new != text:
            with open(path, "w") as f:
                f.write(new)
            print(f"updated {path}")
        else:
            print(f"{path} already up to date")
    for path in args.check:
        with open(path) as f:
            text = f.read()
        if splice(text) != text:
            print(f"{path} is stale: run "
                  f"`python -m repro.tools.docgen --update {path}`",
                  file=sys.stderr)
            stale += 1
        else:
            print(f"{path} generated blocks up to date")
    if not args.update and not args.check:
        print(_block("registry-tables"))
        print()
        print(_block("serving-ops"))
    return 1 if stale else 0


if __name__ == "__main__":
    raise SystemExit(main())
