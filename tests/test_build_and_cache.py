"""The two small repairs under every chip run: where the persistent compile
cache lives, and when the C core's library may be reused.

The build tests swap in a fake compiler (``CXX``) and a one-file source
tree, so they exercise the stamp / lock / replace logic in milliseconds
without compiling the real core.
"""

import json
import os
import stat
import subprocess
import sys

import jax
import pytest

from byteps_tpu.core import build as build_mod
from byteps_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- compile cache ----------------------------------------------------------

@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_set_means_no_config_change(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_unset_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want  # no pid, no time
    assert jax.config.jax_compilation_cache_dir == want
    # ... and the same in another process
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c",
         "from byteps_tpu.utils.compile_cache import enable_compile_cache;"
         "import jax; print(enable_compile_cache());"
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.split() == [want, want]


def test_cache_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# --- build() ----------------------------------------------------------------

_FAKE_CXX = """#!/bin/sh
# fake compiler: logs each real (-o) invocation, writes a 'library'
out=""
prev=""
for a in "$@"; do
  if [ "$prev" = "-o" ]; then out="$a"; fi
  prev="$a"
done
[ -z "$out" ] && exit 0            # a -fsyntax-only flag probe
echo "$out" >> "{log}"
[ -n "$FAKE_CXX_SLEEP" ] && sleep "$FAKE_CXX_SLEEP"
[ -n "$FAKE_CXX_FAIL" ] && exit 1
echo "library built from: $(cat "{src}")" > "$out"
"""


@pytest.fixture
def fake_tree(tmp_path, monkeypatch):
    """A build whose inputs are one fake source and a fake compiler."""
    csrc = tmp_path / "csrc"
    csrc.mkdir()
    (csrc / "a.cc").write_text("v1")
    (csrc / "a.h").write_text("h1")
    log = tmp_path / "cxx.log"
    cxx = tmp_path / "fake-cxx"
    cxx.write_text(_FAKE_CXX.format(log=log, src=csrc / "a.cc"))
    cxx.chmod(cxx.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("CXX", str(cxx))
    monkeypatch.setattr(build_mod, "CSRC", str(csrc))
    monkeypatch.setattr(build_mod, "SOURCES", ["a.cc"])
    monkeypatch.setattr(build_mod, "LIB_PATH", str(tmp_path / "libcore.so"))

    def compiles():
        return log.read_text().splitlines() if log.exists() else []

    return tmp_path, compiles


def _build():
    return build_mod.build(verbose=False)


def test_build_reuses_only_what_the_stamp_proves(fake_tree):
    tmp, compiles = fake_tree
    lib = _build()
    assert lib == str(tmp / "libcore.so") and len(compiles()) == 1
    assert _build() == lib and len(compiles()) == 1      # proven current
    assert build_mod.build(force=True, verbose=False) == lib
    assert len(compiles()) == 2                          # force rebuilds


@pytest.mark.parametrize("tamper", [
    "stamp_inputs", "stamp_garbage", "stamp_missing", "foreign_lib",
    "source_edit", "header_edit"])
def test_build_rebuilds_when_proof_fails(fake_tree, tamper):
    tmp, compiles = fake_tree
    lib = _build()
    stamp = lib + ".stamp"
    if tamper == "stamp_inputs":
        doc = json.loads(open(stamp).read())
        doc["inputs"] = "0" * 64
        open(stamp, "w").write(json.dumps(doc))
    elif tamper == "stamp_garbage":
        open(stamp, "w").write("{not json")
    elif tamper == "stamp_missing":
        os.remove(stamp)
    elif tamper == "foreign_lib":
        # a library that came along in a copy, newer than every source:
        # the old mtime test would have handed it back
        open(lib, "w").write("built elsewhere, for another CPU")
    elif tamper == "source_edit":
        (tmp / "csrc" / "a.cc").write_text("v2")
    elif tamper == "header_edit":
        (tmp / "csrc" / "a.h").write_text("h2")
    assert _build() == lib
    assert len(compiles()) == 2
    assert open(lib).read().startswith("library built from:")
    assert _build() == lib and len(compiles()) == 2      # proven again


def test_build_output_appears_atomically(fake_tree, monkeypatch):
    tmp, compiles = fake_tree
    lib = _build()
    # the compiler never writes the final path: it lands by os.replace
    assert compiles() == [lib + ".tmp"]
    assert not os.path.exists(lib + ".tmp")
    # a failed compile leaves the proven library and its stamp untouched
    good = open(lib).read(), open(lib + ".stamp").read()
    monkeypatch.setenv("FAKE_CXX_FAIL", "1")
    with pytest.raises(subprocess.CalledProcessError):
        build_mod.build(force=True, verbose=False)
    assert (open(lib).read(), open(lib + ".stamp").read()) == good
    assert not os.path.exists(lib + ".tmp")


def test_roles_starting_together_compile_once(fake_tree):
    """Scheduler, server and worker each call build() at load; on a clean
    tree one compiles under the lock and the others reuse its library."""
    tmp, compiles = fake_tree
    code = (
        "import byteps_tpu.core.build as b;"
        f"b.CSRC={build_mod.CSRC!r}; b.SOURCES=['a.cc'];"
        f"b.LIB_PATH={build_mod.LIB_PATH!r};"
        "print(b.build(verbose=False))")
    env = dict(os.environ, PYTHONPATH=REPO, FAKE_CXX_SLEEP="1")
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE, text=True)
             for _ in range(3)]
    outs = [p.communicate(timeout=120)[0].strip() for p in procs]
    assert [p.returncode for p in procs] == [0, 0, 0]
    assert outs == [build_mod.LIB_PATH] * 3
    assert len(compiles()) == 1
