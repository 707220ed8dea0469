"""docs/ freshness + presence (reference ships docs/ as product
surface: architecture notes, how_to, env-var table)."""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


def test_env_var_doc_is_fresh():
    """docs/how_to/env_var.md must match the config registry exactly —
    regenerate with tools/gen_env_doc.py after editing config.py."""
    import gen_env_doc

    with open(os.path.join(ROOT, "docs", "how_to", "env_var.md")) as f:
        on_disk = f.read()
    assert on_disk == gen_env_doc.render(), \
        "docs/how_to/env_var.md is stale: run python tools/gen_env_doc.py"


def test_architecture_note_covers_engine_mapping():
    p = os.path.join(ROOT, "docs", "architecture", "engine_to_xla.md")
    text = open(p).read()
    # the load-bearing claims the note must keep explaining
    for needle in ("dependency", "jax.jit", "PJRT", "donate",
                   "jax.checkpoint", "pure_callback", "lax.scan",
                   "WaitToRead"):
        assert needle in text, needle


def test_multi_device_howto_covers_all_axes():
    p = os.path.join(ROOT, "docs", "how_to", "multi_device.md")
    text = open(p).read()
    for needle in ("PipelineModule", "mx.sym.MoE", "RingAttention",
                   "sharding_map", "group2ctx", "dryrun_multichip",
                   "multihost", "launch.py"):
        assert needle in text, needle


# ----------------------------------------------------------------------
# documents follow the tree
# ----------------------------------------------------------------------

DOCUMENTS = (["README.md"]
             + sorted(os.path.relpath(p, ROOT) for p in glob.glob(
                 os.path.join(ROOT, "docs", "**", "*.md"), recursive=True))
             + [os.path.join("benchmarks", "README.md"),
                os.path.join(".claude", "skills", "verify", "SKILL.md")])

# `python [-u] path.py`, `python -m package.module`, and a back-quoted
# path under one of the repo's script directories
_COMMAND = re.compile(
    r"python3?\s+(?:-[uBOE]+\s+)*(-m\s+[\w.]+|[\w./-]+\.py)")
_QUOTED = re.compile(r"`((?:tools|tests|benchmarks|examples)/[\w./-]+\.py)")
# names a document gives to the READER's own program
_READERS_OWN = {"train.py", "script.py", "your_script.py",
                "serve_my_model.py"}


def _resolves(ref, doc_dir):
    if ref.startswith("-m"):
        parts = ref.split()[1].split(".")
        if not os.path.isdir(os.path.join(ROOT, parts[0])):
            return True     # an installed module (pytest), not the repo's
        base = os.path.join(ROOT, *parts)
        return (os.path.isfile(base + ".py")
                or os.path.isfile(os.path.join(base, "__main__.py")))
    if ref in _READERS_OWN:
        return True
    return any(os.path.isfile(os.path.join(d, ref))
               for d in (ROOT, os.path.join(ROOT, doc_dir)))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_that_exist(doc):
    """Every command and script path a document quotes resolves to a
    file in the tree (from the root, or from the document's own
    directory): a deleted tool takes its instructions with it."""
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    refs = {m.group(1) for m in _COMMAND.finditer(text)}
    refs |= {m.group(1) for m in _QUOTED.finditer(text)}
    missing = sorted(r for r in refs
                     if not _resolves(r, os.path.dirname(doc)))
    assert not missing, "%s names files that are not there: %s" % (
        doc, missing)


def test_importing_the_package_leaves_the_environment_alone(tmp_path):
    """`import mxnet_tpu` reads os.environ and never writes it — not
    even when a variable of the deleted offline tuner points at a
    profile that names registered variables, nor when a retired
    variable is set: that one it names, once, on stderr."""
    profile = tmp_path / "profile.json"
    profile.write_text(json.dumps({
        "schema": "mxtpu-tuned-v1",
        "models": {"m": {"knobs": {"MXTPU_STEPS_PER_DISPATCH": "4"}}}}))
    env = dict(os.environ, MXTPU_TUNED_FILE=str(profile),
               MXTPU_TUNED_MODEL="m", MXTPU_FROZEN_BN="1")
    env.pop("MXTPU_STEPS_PER_DISPATCH", None)
    code = ("import os, json; before = dict(os.environ); "
            "import mxnet_tpu; after = dict(os.environ); "
            "print(json.dumps(sorted(k for k in set(before) | set(after) "
            "if before.get(k) != after.get(k))))")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-4000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == []
    assert r.stderr.count("MXTPU_FROZEN_BN is set and has no effect: "
                          "Module.fit(frozen_bn=False)") == 1, r.stderr
