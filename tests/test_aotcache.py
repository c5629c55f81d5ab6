"""Durable AOT executable cache (serve.aotcache) — the warm-resume
contract and its hardening.

The claims under test, in the ISSUE's words: a second process resumes
from the cache with ZERO ``jit.retrace{fn=life_batch_*}`` ticks and
oracle parity on every resolved ticket; a corrupt/truncated/key-stale
artifact is quarantined and the daemon falls back to a fresh trace with
``aot:*:corrupt``/``aot:*:stale`` provenance, losing nothing; and the
parity gate catches even a CRC-valid artifact that computes wrong
answers. All on the 8-virtual-device CPU mesh — ``jax.export``
serializes the CPU lowering exactly as it would the TPU one.
"""

import glob
import json
import os

import numpy as np
import pytest

from conftest import oracle_n
from mpi_and_open_mp_tpu.obs import metrics
from mpi_and_open_mp_tpu.robust import chaos
from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon
from mpi_and_open_mp_tpu.serve import aotcache


def _life_batch_retraces() -> dict:
    return {k: v for k, v in metrics.snapshot()["counters"].items()
            if k.startswith("jit.retrace{fn=life_batch")}


# -- keying ----------------------------------------------------------------


def test_bucket_sizes_enumeration():
    assert aotcache.bucket_sizes(8) == [1, 2, 4, 8]
    assert aotcache.bucket_sizes(1) == [1]
    assert aotcache.bucket_sizes(6) == [1, 2, 4, 6]  # cap is literal


def test_fingerprint_sensitivity():
    """Every field that can change the compiled program changes the
    digest; identical inputs reproduce it (the filename is the key)."""
    base = aotcache.fingerprint((4, 16, 16), np.uint8)
    assert base["steps"] == aotcache.STEPS_SIGNATURE
    assert base["bucket"] == 4 and base["shape"] == [16, 16]
    assert base["code"] == aotcache.code_fingerprint()
    d = aotcache.digest_for(base)
    assert d == aotcache.digest_for(aotcache.fingerprint((4, 16, 16),
                                                         np.uint8))
    others = [
        aotcache.fingerprint((8, 16, 16), np.uint8),   # bucket
        aotcache.fingerprint((4, 16, 24), np.uint8),   # shape
        aotcache.fingerprint((4, 16, 16), np.int32),   # dtype
        dict(base, jax="0.0.0"),                       # version skew
        dict(base, code="f" * 16),                     # edited kernels
    ]
    digests = {aotcache.digest_for(k) for k in others}
    assert d not in digests and len(digests) == 5


# -- round trip + the zero-retrace guarantee -------------------------------


def test_cold_build_then_warm_hit_zero_retraces(tmp_path, make_board):
    """The tentpole proof: pass 1 builds (ticking the honest compile
    counter once per bucket) and persists; pass 2 — a fresh AOTCache,
    i.e. a restarted process's view — deserializes every program and
    runs it with ZERO life_batch retrace ticks, bit-exact."""
    import jax

    # An earlier test in this process may already hold these programs in
    # the in-memory jit cache, which would hide the cold build's traces.
    jax.clear_caches()
    metrics.reset()
    c1 = aotcache.AOTCache(tmp_path)
    w1 = c1.warm([((16, 16), "uint8")], 4)
    assert w1 == {"hits": 0, "misses": 3, "corrupt": 0, "stale": 0,
                  "parity_failed": 0, "built": 3, "errors": 0,
                  "deserialize_s": 0.0,
                  "build_s": w1["build_s"], "programs": 3}
    assert w1["build_s"] > 0
    assert _life_batch_retraces() == {"jit.retrace{fn=life_batch_xla}": 3}
    assert len(glob.glob(str(tmp_path / "*.aot"))) == 3

    metrics.reset()
    c2 = aotcache.AOTCache(tmp_path)
    w2 = c2.warm([((16, 16), "uint8")], 4)
    assert w2["hits"] == 3 and w2["misses"] == 0 and w2["built"] == 0
    assert w2["deserialize_s"] > 0
    board = make_board(16, 16)
    stack = np.stack([np.asarray(board)] * 2)
    digest, exp, status = c2.ensure(stack.shape, stack.dtype)
    assert status == "memory" and exp is not None
    out = c2.call_verified(digest, stack, 5)
    np.testing.assert_array_equal(out[0], oracle_n(board, 5))
    # steps is a runtime scalar: the SAME program serves other counts.
    out2 = c2.call_verified(digest, stack, 9)
    np.testing.assert_array_equal(out2[0], oracle_n(board, 9))
    assert _life_batch_retraces() == {}


def test_truncated_artifact_quarantined_and_rebuilt(tmp_path):
    aotcache.AOTCache(tmp_path).warm([((12, 12), "uint8")], 1)
    (art,) = glob.glob(str(tmp_path / "*.aot"))
    with open(art, "r+b") as fd:
        fd.truncate(30)  # inside the header
    c = aotcache.AOTCache(tmp_path)
    digest, exp, status = c.ensure((1, 12, 12), np.uint8)
    assert status == "corrupt" and exp is not None  # rebuilt in place
    assert c.stats()["corrupt"] == 1 and c.stats()["built"] == 1
    q = glob.glob(art + ".corrupt.*")
    assert len(q) == 1  # forensic copy, stamped
    assert os.path.exists(art)  # fresh artifact re-persisted
    # And the replacement round-trips clean.
    _, _, status2 = aotcache.AOTCache(tmp_path).ensure((1, 12, 12),
                                                       np.uint8)
    assert status2 == "hit"


def test_stale_key_artifact_rejected(tmp_path):
    """A CRC-valid envelope whose stored fingerprint drifted (here: the
    code hash — edited kernels) is stale, quarantined, rebuilt."""
    key = aotcache.fingerprint((1, 12, 12), np.uint8)
    c0 = aotcache.AOTCache(tmp_path)
    digest, exp, _ = c0.ensure((1, 12, 12), np.uint8)
    path = str(tmp_path / (digest + ".aot"))
    aotcache.save_artifact(path, dict(key, code="0" * 16),
                           exp.serialize())
    c = aotcache.AOTCache(tmp_path)
    _, exp2, status = c.ensure((1, 12, 12), np.uint8)
    assert status == "stale" and exp2 is not None
    assert glob.glob(path + ".stale.*")


def test_parity_gate_catches_wrong_program(tmp_path, make_board):
    """The last line of defense: an artifact that is bit-perfect on disk
    but computes the WRONG function (here: identity instead of Life)
    fails the first-use oracle gate — quarantined, evicted, raised."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export

    key = aotcache.fingerprint((1, 12, 12), np.uint8)
    digest = aotcache.digest_for(key)
    wrong = jax_export.export(jax.jit(lambda boards, steps: boards))(
        jax.ShapeDtypeStruct((1, 12, 12), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32))
    path = str(tmp_path / (digest + ".aot"))
    aotcache.save_artifact(path, key, wrong.serialize())

    c = aotcache.AOTCache(tmp_path)
    got, exp, status = c.ensure((1, 12, 12), np.uint8)
    assert got == digest and status == "hit"  # envelope + key check out
    stack = np.asarray(make_board(12, 12))[None]
    with pytest.raises(aotcache.ParityError, match="oracle"):
        c.call_verified(digest, stack, 3)
    assert c.stats()["parity_failed"] == 1
    assert glob.glob(path + ".corrupt.*")  # artifact quarantined
    # Evicted from memory: the next ensure is a rebuild, and it serves.
    _, exp2, status2 = c.ensure((1, 12, 12), np.uint8)
    assert status2 == "miss" and exp2 is not None
    out = c.call_verified(digest, stack, 3)
    np.testing.assert_array_equal(out[0], oracle_n(stack[0], 3))


# -- chaos tokens ----------------------------------------------------------


def test_chaos_token_parse_and_budget(monkeypatch):
    for spec, kind, k in [("aot_corrupt=bitflip:2", "bitflip", 2),
                          ("aot_corrupt=skew", "skew", 1)]:
        plan = chaos.FaultPlan.parse(spec)
        assert (plan.aot_corrupt_kind, plan.aot_corrupt) == (kind, k)
    for bad in ["aot_corrupt=gamma:1", "aot_corrupt=bitflip:0",
                "aot_corrupt="]:
        with pytest.raises(ValueError, match="MOMP_CHAOS"):
            chaos.FaultPlan.parse(bad)

    monkeypatch.setenv("MOMP_CHAOS", "aot_corrupt=bitflip:2")
    chaos.reset()
    assert chaos.take_aot_corrupt() == "bitflip"
    with chaos.suppressed():
        assert chaos.take_aot_corrupt() is None  # recovery writes clean
    assert chaos.take_aot_corrupt() == "bitflip"
    assert chaos.take_aot_corrupt() is None  # budget spent
    chaos.reset()


@pytest.mark.parametrize("kind,status", [("bitflip", "corrupt"),
                                         ("skew", "stale")])
def test_chaos_corrupts_artifact_at_save(tmp_path, monkeypatch, kind,
                                         status):
    """The drill the CI job runs in-process: the plan damages the FIRST
    saved artifact on disk (the saving process's resident program stays
    good), and the next process's load takes exactly the planned
    rejection path, quarantines, rebuilds, and serves."""
    monkeypatch.setenv("MOMP_CHAOS", f"aot_corrupt={kind}:1")
    chaos.reset()
    c1 = aotcache.AOTCache(tmp_path)
    w = c1.warm([((12, 12), "uint8")], 2)
    assert w["built"] == 2  # both programs fine in memory
    monkeypatch.delenv("MOMP_CHAOS")
    chaos.reset()

    c2 = aotcache.AOTCache(tmp_path)
    w2 = c2.warm([((12, 12), "uint8")], 2)
    assert w2[status] == 1 and w2["hits"] == 1 and w2["built"] == 1
    assert len(glob.glob(str(tmp_path / f"*.{status}.*"))) == 1


# -- daemon integration ----------------------------------------------------


def test_daemon_cold_warm_cycle_books_and_provenance(tmp_path,
                                                     make_board):
    """Cold daemon populates the cache and serves through the aot rung;
    a second 'process' (fresh cache + daemon + metrics) serves the same
    shapes warm: all hits, zero retraces, every board oracle-exact,
    books balanced."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    boards = [make_board(16, 16) for _ in range(6)]

    metrics.reset()
    d1 = ServingDaemon(pol, aot_cache=aotcache.AOTCache(tmp_path))
    d1._aot.warm([((16, 16), "uint8")], pol.max_batch)
    for b in boards:
        d1.submit(b, 3)
    d1.serve(watch_signals=False)
    s1 = d1.summary()
    assert s1["resolved"] == 6 and s1["engines"] == {"aot:xla": 6}
    assert s1["aot_misses"] == 3 and s1["cold_first_result_s"] > 0

    metrics.reset()
    d2 = ServingDaemon(pol, aot_cache=aotcache.AOTCache(tmp_path))
    d2._aot.warm([((16, 16), "uint8")], pol.max_batch)
    for b in boards:
        d2.submit(b, 7)
    d2.serve(watch_signals=False)
    s2 = d2.summary()
    assert s2["requests"] == s2["resolved"] == 6 and s2["shed"] == 0
    assert s2["engines"] == {"aot:xla": 6}
    assert s2["aot_hits"] == 3 and s2["aot_misses"] == 0
    assert s2["aot_deserialize_s"] > 0 and s2["aot_build_s"] == 0
    assert _life_batch_retraces() == {}
    for t, b in zip(d2.queue.tickets(), boards):
        np.testing.assert_array_equal(t.result, oracle_n(b, 7))


def test_daemon_corrupt_cache_falls_back_with_provenance(tmp_path,
                                                         make_board):
    """A rotten artifact mid-cache costs a rebuild, never a ticket: the
    dispatch stamps carry the `aot:*:corrupt` provenance and the whole
    burst still resolves oracle-exact."""
    pol = ServePolicy(max_batch=2, max_wait_s=0.0)
    aotcache.AOTCache(tmp_path).warm([((12, 12), "uint8")], 2)
    for art in glob.glob(str(tmp_path / "*.aot")):
        with open(art, "r+b") as fd:
            fd.seek(60)
            fd.write(b"\xde\xad\xbe\xef")  # CRC breaks on next load
    d = ServingDaemon(pol, aot_cache=aotcache.AOTCache(tmp_path))
    boards = [make_board(12, 12) for _ in range(4)]
    for b in boards:
        d.submit(b, 2)
    d.serve(watch_signals=False)
    s = d.summary()
    assert s["resolved"] == 4 and s["shed"] == 0
    assert set(s["engines"]) <= {"aot:xla:corrupt", "aot:xla"}
    assert "aot:xla:corrupt" in s["engines"]
    assert s["aot_corrupt"] >= 1
    for t, b in zip(d.queue.tickets(), boards):
        np.testing.assert_array_equal(t.result, oracle_n(b, 2))


def test_resume_any_preloads_pending_shapes(tmp_path, make_board):
    """The resume preload phase: a WAL left by a dead daemon resumes
    with the cache attached; every bucket program for the restored
    pending set is resident BEFORE the first dispatch, and the drain
    runs entirely on the aot rung with zero retraces."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    walp = str(tmp_path / "serve.wal")
    cache_dir = tmp_path / "aot"
    aotcache.AOTCache(cache_dir).warm([((16, 16), "uint8")], 4)

    # Process 1: admits but never dispatches (dies with a populated WAL).
    d1 = ServingDaemon(pol, wal_path=walp)
    boards = [make_board(16, 16) for _ in range(5)]
    for b in boards:
        d1.submit(b, 4)
    d1._wal.sync()

    metrics.reset()
    d2, source, detail = ServingDaemon.resume_any(
        wal_path=walp, policy=pol,
        aot_cache=aotcache.AOTCache(cache_dir))
    assert source == "wal" and d2.queue.depth() == 5
    pre = detail["aot_preload"]
    assert pre["hits"] == 3 and pre["misses"] == 0  # warm: pure deser
    d2.serve(watch_signals=False)
    s = d2.summary()
    assert s["resolved"] == 5 and s["engines"] == {"aot:xla": 5}
    assert _life_batch_retraces() == {}
    for t, b in zip(d2.queue.tickets(), boards):
        np.testing.assert_array_equal(t.result, oracle_n(b, 4))
    d2._wal.close()


def test_daemon_cli_aot_flag_and_env(tmp_path, capsys, monkeypatch):
    """CLI surface: --aot-cache stamps the warm/hit accounting on the
    line; MOMP_AOT_CACHE is the env twin; without either the line
    carries no aot fields (the cache is strictly opt-in)."""
    from mpi_and_open_mp_tpu.serve import daemon as daemon_cli

    cache_dir = str(tmp_path / "aot")
    rc = daemon_cli.main(["--requests", "6", "--max-batch", "4",
                          "--max-wait", "0", "--shapes", "16x16",
                          "--aot-cache", cache_dir, "--verify"])
    line = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and line["verified"] is True
    assert line["aot_cache"] == os.path.abspath(cache_dir)
    assert line["aot_warm"]["built"] == 3
    assert line["engines"] == {"aot:xla": 6}
    assert line["cold_first_result_s"] > 0

    monkeypatch.setenv("MOMP_AOT_CACHE", cache_dir)
    rc = daemon_cli.main(["--requests", "6", "--max-batch", "4",
                          "--max-wait", "0", "--shapes", "16x16",
                          "--verify"])
    line2 = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and line2["verified"] is True
    assert line2["aot_warm"]["hits"] == 3  # env twin found the artifacts
    assert line2["aot_misses"] == 0
    monkeypatch.delenv("MOMP_AOT_CACHE")

    rc = daemon_cli.main(["--requests", "2", "--max-batch", "2",
                          "--max-wait", "0", "--shapes", "16x16"])
    line3 = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and "aot_cache" not in line3 and "aot" not in line3
