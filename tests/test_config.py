"""Config-system tests (env-var parity, SURVEY.md §5)."""

import pytest

from byteps_tpu.config import Config, load_config


def test_defaults(monkeypatch):
    for var in ("DMLC_ROLE", "DMLC_NUM_WORKER", "BYTEPS_PARTITION_BYTES"):
        monkeypatch.delenv(var, raising=False)
    cfg = load_config()
    assert cfg.role == "worker"
    assert cfg.partition_bytes == 4096000
    # byte budget; 0 = auto (10 x partition_bytes, resolved in the C core)
    assert cfg.scheduling_credit == 0
    assert not cfg.distributed
    assert not cfg.use_ps


def test_legacy_partition_count_credit_warns_passthrough(monkeypatch):
    """BYTEPS_SCHEDULING_CREDIT is now a byte budget; a tiny value can
    only be a legacy partition count. The Python layer warns but passes
    the value through unchanged — the C core is the single conversion
    point (credit x partition_bytes), so the two layers can never
    compose a double conversion and validate() stays idempotent."""
    monkeypatch.setenv("BYTEPS_SCHEDULING_CREDIT", "4")
    import pytest
    with pytest.warns(UserWarning, match="legacy in-flight partition"):
        cfg = load_config().validate()
    assert cfg.scheduling_credit == 4
    with pytest.warns(UserWarning):
        cfg.validate()  # idempotent: same warning, value still unchanged
    assert cfg.scheduling_credit == 4


@pytest.mark.parametrize("credit,says", [
    (-1, "0 = auto: 10 x BYTEPS_PARTITION_BYTES"),
    (4, "set 0 for auto = 10 x BYTEPS_PARTITION_BYTES"),
], ids=["negative", "legacy-count"])
def test_credit_messages_name_the_default_in_force(credit, says):
    """ISSUE 48: what `0` stands for is ten partitions (resolved in
    `worker.cc`); the error and the warning that send a user there say
    so."""
    import warnings
    cfg = Config(scheduling_credit=credit)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        if credit < 0:
            with pytest.raises(ValueError) as err:
                cfg.validate()
            text = str(err.value)
        else:
            cfg.validate()
            text = " ".join(str(w.message) for w in caught)
    assert says in text, text


@pytest.mark.parametrize("path,phrase", [
    ("byteps_tpu/config.py", "0 = auto: {n} x BYTEPS_PARTITION_BYTES"),
    ("docs/env.md", "`0` = auto: {n} × `BYTEPS_PARTITION_BYTES`"),
    ("docs/best-practice.md", "default {n} × partition)"),
    ("docs/troubleshooting.md", "({n} x partition bytes)"),
    ("docs/monitoring.md", "to hold against the credit's {n})"),
    ("PARITY.md", "(0 = {n} × partition"),
], ids=["config", "env", "best-practice", "troubleshooting", "monitoring",
        "parity"])
def test_default_credit_reads_the_same_wherever_it_is_named(path, phrase):
    """The default is resolved in one place, `worker.cc::Start`; every file
    that tells a user what `0` stands for names the number that is there."""
    import os
    import re
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "byteps_tpu/core/csrc/worker.cc")) as f:
        found = re.findall(r"credit_bytes = (\d+) \* partition_bytes;",
                           f.read())
    assert len(found) == 1, found
    with open(os.path.join(root, path)) as f:
        assert phrase.format(n=found[0]) in f.read(), (path, found[0])


def test_env_parity_names(monkeypatch):
    monkeypatch.setenv("DMLC_ROLE", "server")
    monkeypatch.setenv("DMLC_NUM_WORKER", "4")
    monkeypatch.setenv("DMLC_NUM_SERVER", "2")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "10.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", "1234")
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "1048576")
    monkeypatch.setenv("BYTEPS_SCHEDULING_CREDIT", "8388608")
    monkeypatch.setenv("BYTEPS_ENABLE_ASYNC", "1")
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("BYTEPS_LOG_LEVEL", "debug")
    cfg = load_config()
    assert cfg.role == "server"
    assert cfg.num_worker == 4 and cfg.num_server == 2
    assert cfg.root_uri == "10.0.0.1" and cfg.root_port == 1234
    assert cfg.partition_bytes == 1 << 20
    assert cfg.scheduling_credit == 8 << 20
    assert cfg.enable_async and cfg.force_distributed and cfg.distributed
    assert cfg.use_ps
    assert cfg.log_level == "DEBUG"


def test_fusion_defaults_and_env(monkeypatch):
    """Small-tensor fusion knobs (ISSUE 2): sensible defaults, env
    override, and 0 as the documented off switch."""
    for var in ("BYTEPS_FUSION_BYTES", "BYTEPS_FUSION_KEYS",
                "BYTEPS_FUSION_LINGER_US"):
        monkeypatch.delenv(var, raising=False)
    cfg = load_config()
    assert cfg.fusion_bytes == 65536
    assert cfg.fusion_keys == 128
    assert cfg.fusion_linger_us == 200
    monkeypatch.setenv("BYTEPS_FUSION_BYTES", "0")  # fusion off
    monkeypatch.setenv("BYTEPS_FUSION_KEYS", "32")
    monkeypatch.setenv("BYTEPS_FUSION_LINGER_US", "0")
    cfg = load_config()
    assert cfg.fusion_bytes == 0
    assert cfg.fusion_keys == 32
    assert cfg.fusion_linger_us == 0


def test_fusion_validation():
    with pytest.raises(ValueError, match="BYTEPS_FUSION_BYTES"):
        Config(fusion_bytes=-1).validate()
    with pytest.raises(ValueError, match="BYTEPS_FUSION_KEYS"):
        Config(fusion_keys=1).validate()
    with pytest.raises(ValueError, match="BYTEPS_FUSION_LINGER_US"):
        Config(fusion_linger_us=-5).validate()
    Config(fusion_bytes=0).validate()  # 0 = off is legal
    # fusion_keys is only meaningful while fusion is on: an explicitly
    # disabled config must not fail startup over it (the C core clamps
    # the same value with a warning instead of erroring).
    Config(fusion_bytes=0, fusion_keys=1).validate()


def test_invalid_role():
    with pytest.raises(ValueError):
        Config(role="bogus").validate()


def test_ps_mode_override():
    assert not Config(num_server=2, ps_mode="collective").use_ps
    assert Config(ps_mode="ps").use_ps


def test_heartbeat_timeout_must_exceed_interval():
    """ISSUE 3 satellite: a timeout at-or-below the interval declares
    healthy nodes dead on their first missed tick — reject it at init
    with the fix named, instead of letting the fleet kill itself."""
    with pytest.raises(ValueError, match="PS_HEARTBEAT_TIMEOUT"):
        Config(heartbeat_interval_s=5.0, heartbeat_timeout_s=5.0).validate()
    with pytest.raises(ValueError, match="PS_HEARTBEAT_TIMEOUT"):
        Config(heartbeat_interval_s=5.0, heartbeat_timeout_s=2.0).validate()
    Config(heartbeat_interval_s=1.0, heartbeat_timeout_s=3.0).validate()
    # Heartbeats disabled (<= 0): the relation is vacuous, any timeout ok.
    Config(heartbeat_interval_s=0.0, heartbeat_timeout_s=0.0).validate()


def test_retry_and_chaos_validation():
    """Fault-tolerance knobs (ISSUE 3): ranges enforced, and chaos
    injection refuses to arm without the retry layer that absorbs it."""
    with pytest.raises(ValueError, match="BYTEPS_RETRY_MAX"):
        Config(retry_max=-1).validate()
    with pytest.raises(ValueError, match="BYTEPS_RETRY_TIMEOUT_MS"):
        Config(retry_timeout_ms=5).validate()
    with pytest.raises(ValueError, match="BYTEPS_RECONNECT_MAX"):
        Config(reconnect_max=0).validate()
    with pytest.raises(ValueError, match="BYTEPS_CHAOS_DROP"):
        Config(chaos_drop=1.0).validate()
    with pytest.raises(ValueError, match="BYTEPS_CHAOS_DUP"):
        Config(chaos_dup=-0.1).validate()
    with pytest.raises(ValueError, match="BYTEPS_CHAOS_RESET_EVERY"):
        Config(chaos_reset_every=-1).validate()
    # Chaos without retry would just crash the fleet at the first fault.
    with pytest.raises(ValueError, match="BYTEPS_RETRY_MAX > 0"):
        Config(chaos_drop=0.01, retry_max=0).validate()
    # Retry off alone is a legal (documented) escape hatch...
    Config(retry_max=0).validate()
    # ...and delay-only chaos needs no retry (nothing is ever lost).
    Config(chaos_delay_us=100, retry_max=0).validate()


def test_chaos_env_roundtrip(monkeypatch):
    monkeypatch.setenv("BYTEPS_CHAOS_SEED", "42")
    monkeypatch.setenv("BYTEPS_CHAOS_DROP", "0.05")
    monkeypatch.setenv("BYTEPS_CHAOS_DUP", "0.01")
    monkeypatch.setenv("BYTEPS_CHAOS_DELAY_US", "250")
    monkeypatch.setenv("BYTEPS_CHAOS_RESET_EVERY", "500")
    monkeypatch.setenv("BYTEPS_RETRY_MAX", "6")
    monkeypatch.setenv("BYTEPS_RETRY_TIMEOUT_MS", "400")
    cfg = load_config()
    assert cfg.chaos_seed == 42
    assert cfg.chaos_drop == 0.05 and cfg.chaos_dup == 0.01
    assert cfg.chaos_delay_us == 250 and cfg.chaos_reset_every == 500
    assert cfg.retry_max == 6 and cfg.retry_timeout_ms == 400


def test_recovery_knob_validation():
    """Hot-server-replacement knobs (ISSUE 4): ranges enforced, the
    recovery window must clear a heartbeat round trip, and a replacement
    incarnation (DMLC_RECOVER_RANK) only makes sense on a server process
    in a fleet where recovery can actually run."""
    with pytest.raises(ValueError, match="BYTEPS_RECOVERY_TIMEOUT_MS"):
        Config(recovery_timeout_ms=-1).validate()
    # The window must exceed PS_HEARTBEAT_TIMEOUT: a replacement cannot
    # even register before the scheduler notices the death.
    with pytest.raises(ValueError, match="must exceed PS_HEARTBEAT_TIMEOUT"):
        Config(recovery_timeout_ms=5000, heartbeat_interval_s=1.0,
               heartbeat_timeout_s=30.0).validate()
    Config(recovery_timeout_ms=60000, heartbeat_interval_s=1.0,
           heartbeat_timeout_s=30.0).validate()
    # Heartbeats disabled: no death detection, relation vacuous.
    Config(recovery_timeout_ms=5000, heartbeat_interval_s=0.0).validate()
    # DMLC_RECOVER_RANK: server-only, in range, and recovery must be on.
    Config(role="server", num_server=2, recover_rank=1).validate()
    with pytest.raises(ValueError, match="server-process knob"):
        Config(role="worker", num_server=2, recover_rank=1).validate()
    with pytest.raises(ValueError, match="out of range"):
        Config(role="server", num_server=2, recover_rank=2).validate()
    with pytest.raises(ValueError, match="DMLC_RECOVER_RANK is set but"):
        Config(role="server", num_server=2, recover_rank=0,
               recovery_timeout_ms=0).validate()


def test_recovery_requires_retry_implicitly():
    """Re-seed rides the resend queue, so BYTEPS_RETRY_MAX=0 keeps its
    documented restore-fail-fast-wholesale meaning: recovery is
    implicitly off (effective window 0, projected to the C core), not a
    validation error — but a replacement incarnation under retry-off IS
    an error, because its re-seed could never arrive."""
    cfg = Config(retry_max=0).validate()
    assert cfg.recovery_timeout_ms == 60000  # raw knob untouched
    assert cfg.effective_recovery_timeout_ms == 0
    assert Config(retry_max=4).effective_recovery_timeout_ms == 60000
    with pytest.raises(ValueError, match="BYTEPS_RETRY_MAX=0"):
        Config(role="server", num_server=2, recover_rank=1,
               retry_max=0).validate()


def test_trace_dir_env_unification(monkeypatch):
    """ISSUE 5 satellite: BYTEPS_TRACE_DIR is canonical, the legacy
    BPS_TRACE_OUT still works as an alias, and a conflicting pair warns
    with the canonical name winning."""
    monkeypatch.delenv("BYTEPS_TRACE_DIR", raising=False)
    monkeypatch.delenv("BPS_TRACE_OUT", raising=False)
    assert load_config().trace_dir == "./traces"
    monkeypatch.setenv("BPS_TRACE_OUT", "/tmp/legacy")
    assert load_config().trace_dir == "/tmp/legacy"
    monkeypatch.setenv("BYTEPS_TRACE_DIR", "/tmp/canonical")
    with pytest.warns(UserWarning, match="BPS_TRACE_OUT"):
        cfg = load_config()
    assert cfg.trace_dir == "/tmp/canonical"
    # Agreeing values: no warning, no ambiguity.
    monkeypatch.setenv("BPS_TRACE_OUT", "/tmp/canonical")
    assert load_config().trace_dir == "/tmp/canonical"


def test_trace_window_and_ring_validation():
    """ISSUE 5 satellite: the step window must be well-formed (the C
    core enforces it now too), and the ring capacities have floors."""
    with pytest.raises(ValueError, match="BYTEPS_TRACE_END_STEP"):
        Config(trace_start_step=10, trace_end_step=5).validate()
    with pytest.raises(ValueError, match="BYTEPS_TRACE_START_STEP"):
        Config(trace_start_step=0).validate()
    with pytest.raises(ValueError, match="BYTEPS_TRACE_RING_EVENTS"):
        Config(trace_ring_events=4).validate()
    with pytest.raises(ValueError, match="BYTEPS_FLIGHT_RECORDER_EVENTS"):
        Config(flight_recorder_events=2).validate()
    Config(trace_start_step=3, trace_end_step=3).validate()  # 1-step ok


def test_flight_recorder_defaults_and_env(monkeypatch):
    """The flight recorder is ON by default (zero-config failure
    forensics); BYTEPS_FLIGHT_RECORDER=0 is the off switch."""
    for var in ("BYTEPS_FLIGHT_RECORDER", "BYTEPS_FLIGHT_RECORDER_EVENTS",
                "BYTEPS_TRACE_RING_EVENTS"):
        monkeypatch.delenv(var, raising=False)
    cfg = load_config()
    assert cfg.flight_recorder is True
    assert cfg.flight_recorder_events == 256
    assert cfg.trace_ring_events == 65536
    monkeypatch.setenv("BYTEPS_FLIGHT_RECORDER", "0")
    monkeypatch.setenv("BYTEPS_FLIGHT_RECORDER_EVENTS", "64")
    monkeypatch.setenv("BYTEPS_TRACE_RING_EVENTS", "1024")
    cfg = load_config()
    assert cfg.flight_recorder is False
    assert cfg.flight_recorder_events == 64
    assert cfg.trace_ring_events == 1024


def test_recovery_env_roundtrip(monkeypatch):
    monkeypatch.setenv("DMLC_ROLE", "server")
    monkeypatch.setenv("DMLC_NUM_SERVER", "2")
    monkeypatch.setenv("BYTEPS_RECOVERY_TIMEOUT_MS", "45000")
    monkeypatch.setenv("DMLC_RECOVER_RANK", "1")
    cfg = load_config()
    assert cfg.recovery_timeout_ms == 45000
    assert cfg.recover_rank == 1
    monkeypatch.delenv("DMLC_RECOVER_RANK")
    assert load_config().recover_rank is None


def test_wire_quant_defaults_and_env(monkeypatch):
    """Block-quantized wire knobs (ISSUE 6): off by default (the wire is
    then byte-for-byte the pre-quant protocol), env override works, and
    the values project back into the env the C core reads."""
    for var in ("BYTEPS_WIRE_QUANT", "BYTEPS_WIRE_QUANT_BLOCK",
                "BYTEPS_WIRE_QUANT_MIN_BYTES"):
        monkeypatch.delenv(var, raising=False)
    cfg = load_config()
    assert cfg.wire_quant is False
    assert cfg.wire_quant_block == 64
    assert cfg.wire_quant_min_bytes == 1024
    monkeypatch.setenv("BYTEPS_WIRE_QUANT", "1")
    monkeypatch.setenv("BYTEPS_WIRE_QUANT_BLOCK", "256")
    monkeypatch.setenv("BYTEPS_WIRE_QUANT_MIN_BYTES", "4096")
    cfg = load_config()
    assert cfg.wire_quant is True
    assert cfg.wire_quant_block == 256
    assert cfg.wire_quant_min_bytes == 4096
    import os

    from byteps_tpu.core.ffi import _apply_config_env
    _apply_config_env(cfg)
    assert os.environ["BYTEPS_WIRE_QUANT"] == "1"
    assert os.environ["BYTEPS_WIRE_QUANT_BLOCK"] == "256"
    assert os.environ["BYTEPS_WIRE_QUANT_MIN_BYTES"] == "4096"


def test_wire_quant_block_validation():
    """Block must be a power of two in [16, 32768] — the decode path
    rejects any other geometry as a malformed frame, so the config must
    refuse it before it ever reaches a wire."""
    for bad in (0, 1, 8, 15, 48, 100, 65536, -16):
        with pytest.raises(ValueError, match="BYTEPS_WIRE_QUANT_BLOCK"):
            Config(wire_quant_block=bad).validate()
    for ok in (16, 64, 1024, 32768):
        Config(wire_quant_block=ok).validate()
    with pytest.raises(ValueError, match="BYTEPS_WIRE_QUANT_MIN_BYTES"):
        Config(wire_quant_min_bytes=-1).validate()


def test_wire_quant_compressor_conflict_rejected():
    """BYTEPS_WIRE_QUANT operates on raw float32 payloads; a fleet-wide
    codec puts compressor bytes on every key, so quant would silently
    never engage — the contradiction must fail validation (per-tensor
    compression overrides remain the composing escape hatch)."""
    with pytest.raises(ValueError, match="BYTEPS_WIRE_QUANT"):
        Config(wire_quant=True, compressor="type=onebit").validate()
    Config(wire_quant=True).validate()  # quant alone is fine
    Config(compressor="type=onebit").validate()  # codec alone is fine


def test_wire_quant_async_warns():
    """quant + async is legal but the server accumulator integrates
    lossy deltas with no round boundary for EF to true up against —
    warn loudly."""
    with pytest.warns(UserWarning, match="BYTEPS_WIRE_QUANT"):
        Config(wire_quant=True, enable_async=True).validate()
