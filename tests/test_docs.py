"""Documentation cannot rot: execute every fenced ``python`` block.

Extracts the fenced ``python`` code blocks from README.md and every
``docs/*.md`` guide and runs them — per file, in order, sharing one
namespace (so a guide can build on its earlier snippets, exactly as a
reader would paste them).  Each file runs in a fresh subprocess so
snippet side effects (registering demo ops, rebinding the default
backend) cannot leak into this test process, and with 4 forced host
devices so the sharding guide genuinely exercises a multi-device mesh.

The ``docs-check`` CI job runs exactly this file.
"""
from __future__ import annotations

import os
import pathlib
import re
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

DOC_FILES = sorted([REPO / "README.md"]
                   + list((REPO / "docs").glob("*.md")))

_FENCE = re.compile(r"^```python\s*$(.*?)^```\s*$",
                    re.MULTILINE | re.DOTALL)


def extract_blocks(path: pathlib.Path):
    return [m.group(1) for m in _FENCE.finditer(path.read_text())]


def test_docs_exist_and_have_snippets():
    """README + the three guides exist, each with runnable python."""
    names = {p.name for p in DOC_FILES}
    assert "README.md" in names
    for guide in ("kernels.md", "serving.md", "sharding.md",
                  "streaming.md"):
        assert guide in names, f"docs/{guide} missing"
    for p in DOC_FILES:
        assert extract_blocks(p), f"{p.name} has no fenced python blocks"


@pytest.mark.parametrize("path", DOC_FILES, ids=lambda p: p.name)
def test_doc_snippets_execute(path):
    blocks = extract_blocks(path)
    script = "\n\n".join(
        f"# --- {path.name} block {i} ---\n{b}"
        for i, b in enumerate(blocks))
    from repro.hostdev import force_host_devices

    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    # override any inherited device-count flag: the subprocess is
    # deliberately isolated and the sharding guide expects 4 devices
    force_host_devices(4, env, override=True)
    proc = subprocess.run([sys.executable, "-"], input=script, text=True,
                          capture_output=True, env=env, cwd=str(REPO),
                          timeout=600)
    assert proc.returncode == 0, (
        f"{path.name} snippet failed:\n--- stdout ---\n{proc.stdout}\n"
        f"--- stderr ---\n{proc.stderr}")
