"""Tier-1 lint: every env var Config reads is documented in docs/env.md
(tools/check_env_docs.py — the operator contract must not drift), and
every variable docs/env.md documents is still read by the package."""

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
sys.path.insert(0, TOOLS)

import check_env_docs  # noqa: E402


def test_config_env_vars_found():
    """The scanner must actually see the config surface — an empty result
    would make the doc lint vacuously green."""
    found = check_env_docs.config_env_vars()
    assert len(found) >= 20, sorted(found)
    assert "BYTEPS_MONITOR_PORT" in found
    assert "DMLC_NUM_WORKER" in found


def test_every_config_env_var_documented():
    missing = check_env_docs.undocumented()
    assert not missing, (
        f"Config env vars missing from docs/env.md: {missing} — "
        "document them (tools/check_env_docs.py)")


def test_every_documented_env_var_is_read():
    """The reverse direction: a row of docs/env.md whose variable nothing
    under byteps_tpu/ names any more documents a knob that left."""
    with open(check_env_docs.ENV_MD) as f:
        name_cells = [line.split("|")[1] for line in f
                      if line.startswith("| `")]
    documented = {name for cell in name_cells
                  for name in re.findall(r"`([A-Z][A-Z0-9_]*)`", cell)}
    assert len(documented) >= 100, sorted(documented)
    source = []
    for root, _, files in os.walk(os.path.join(REPO, "byteps_tpu")):
        for name in files:
            if name.endswith((".py", ".cc", ".h")):
                with open(os.path.join(root, name), errors="replace") as f:
                    source.append(f.read())
    source = "\n".join(source)
    stale = sorted(v for v in documented
                   if not re.search(rf"\b{v}\b", source))
    assert not stale, (
        f"docs/env.md documents variables nothing under byteps_tpu/ "
        f"reads: {stale} — drop their rows")
