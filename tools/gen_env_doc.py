#!/usr/bin/env python
"""Regenerate docs/how_to/env_var.md from the config registry — the
single source of truth (mxnet_tpu/config.py).  CI pins freshness
(tests/test_docs.py), matching the reference's docs/how_to/env_var.md."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def render():
    from mxnet_tpu.config import ABSORBED, REGISTRY, RETIRED

    lines = [
        "# Environment variables",
        "",
        "<!-- GENERATED by tools/gen_env_doc.py from mxnet_tpu/config.py —",
        "     edit the registry, not this file -->",
        "",
        "Runtime knobs, each declared once in `mxnet_tpu/config.py` with a",
        "typed accessor (`mx.config.get(name)`).  Parity: the reference",
        "documents its `dmlc::GetEnv` surface in `docs/how_to/env_var.md`.",
        "",
        "| variable | type | default | description |",
        "|---|---|---|---|",
    ]
    for v in REGISTRY:
        lines.append("| `%s` | %s | `%s` | %s |"
                     % (v.name, v.type.__name__, v.default, v.desc))
    lines += [
        "",
        "## Absorbed by XLA/PJRT",
        "",
        "Reference knobs whose resource is owned by the compiler/runtime",
        "here; they are accepted and ignored so migrated scripts get an",
        "answer instead of silence:",
        "",
        "| reference variable | absorbed by |",
        "|---|---|",
    ]
    for k, why in sorted(ABSORBED.items()):
        lines.append("| `%s` | %s |" % (k, why))
    lines += [
        "",
        "## Retired",
        "",
        "Variables an earlier release registered and no code reads any",
        "more.  Setting one has no effect; importing the package with",
        "one set warns once and names what holds the value now:",
        "",
        "| retired variable | what holds the value now |",
        "|---|---|",
    ]
    for k, now in sorted(RETIRED.items()):
        lines.append("| `%s` | %s |" % (k, now))
    lines.append("")
    return "\n".join(lines)


def main():
    out = os.path.join(REPO, "docs", "how_to", "env_var.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(render())
    print("wrote %s" % out)


if __name__ == "__main__":
    main()
