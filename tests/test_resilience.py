"""Resilience layer: fault-injection matrix, OOM degradation ladder,
checkpointed streaming resume, and graceful shard degradation — all on
the CPU tier-1 platform via the deterministic harness
(raft_tpu/resilience/faultinject.py; docs/resilience.md)."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import resilience, tuning
from raft_tpu.core.interruptible import Interruptible, InterruptedException
from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
from raft_tpu.neighbors.stream import search_host_array
from raft_tpu.resilience import checkpoint, degrade, errors, faultinject
from tests.oracles import naive_knn

pytestmark = pytest.mark.resilience


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.clear()
    tuning.reload()
    yield
    faultinject.clear()
    tuning.reload()


# ---------------------------------------------------------------------------
# classification + retry executor
# ---------------------------------------------------------------------------


def test_classify_kinds():
    assert resilience.classify(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 3221225472 bytes"
    )) == resilience.OOM
    assert resilience.classify(RuntimeError(
        "UNAVAILABLE: connection reset by peer")) == resilience.TRANSIENT
    assert resilience.classify(ValueError("shape mismatch")) == resilience.FATAL
    assert resilience.classify(MemoryError()) == resilience.OOM
    assert resilience.classify(InterruptedException("x")) == resilience.INTERRUPTED
    import subprocess

    assert resilience.classify(
        subprocess.TimeoutExpired("cmd", 5)) == resilience.DEAD_BACKEND
    assert resilience.classify(
        faultinject.InjectedOOM("RESOURCE_EXHAUSTED: injected")
    ) == resilience.OOM
    assert resilience.classify(
        faultinject.InjectedDeadBackend("x")) == resilience.DEAD_BACKEND
    assert resilience.classify(
        resilience.TransientError("stage flaked")) == resilience.TRANSIENT


def test_classify_text():
    assert resilience.classify_text("... RESOURCE_EXHAUSTED ...") == resilience.OOM
    assert resilience.classify_text("UNAVAILABLE: socket closed") == resilience.TRANSIENT
    assert resilience.classify_text("assert failed") == resilience.FATAL


def test_run_retries_transient_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise resilience.TransientError("blip")
        return "ok"

    assert resilience.run(flaky, retries=3, backoff_s=0.001) == "ok"
    assert len(calls) == 3


def test_run_retry_budget_exhausted():
    def always():
        raise resilience.TransientError("blip")

    with pytest.raises(resilience.TransientError):
        resilience.run(always, retries=1, backoff_s=0.001)


def test_run_fatal_not_retried():
    calls = []

    def boom():
        calls.append(1)
        raise ValueError("bug")

    with pytest.raises(ValueError):
        resilience.run(boom, retries=3, backoff_s=0.001)
    assert len(calls) == 1


def test_run_oom_not_retried_by_default():
    calls = []

    def oom():
        calls.append(1)
        raise faultinject.InjectedOOM("RESOURCE_EXHAUSTED: injected")

    with pytest.raises(faultinject.InjectedOOM):
        resilience.run(oom, retries=3, backoff_s=0.001)
    assert len(calls) == 1


def test_run_deadline_exceeded():
    def always():
        raise resilience.TransientError("blip")

    t0 = time.monotonic()
    with pytest.raises(resilience.DeadlineExceededError):
        resilience.run(always, retries=50, backoff_s=0.2, deadline_s=0.3)
    assert time.monotonic() - t0 < 5.0


def test_run_dead_backend_probes_then_retries():
    # on CPU the liveness probe answers, so a one-shot dead fault recovers
    calls = []

    def once_dead():
        calls.append(1)
        if len(calls) == 1:
            raise faultinject.InjectedDeadBackend("injected dead-backend")
        return 7

    assert resilience.run(once_dead, retries=2, backoff_s=0.001) == 7
    assert len(calls) == 2


def test_run_cancelled_token_stops():
    tok = Interruptible()
    tok.cancel()
    with pytest.raises(InterruptedException):
        resilience.run(lambda: 1, token=tok)


def test_backend_alive_on_cpu():
    assert resilience.backend_alive(timeout_s=30.0)


# ---------------------------------------------------------------------------
# fault-injection grammar
# ---------------------------------------------------------------------------


def test_faultinject_grammar():
    specs = faultinject.parse("oom@chunk:3,dead@stage:search,shard@rank:2")
    assert [(s.kind, s.scope, s.arg) for s in specs] == [
        ("oom", "chunk", "3"), ("dead", "stage", "search"),
        ("shard", "rank", "2"),
    ]
    (s,) = faultinject.parse("oom@chunk:1*2")
    assert s.remaining == 2
    (s,) = faultinject.parse("dead@stage:build.pass2#3")
    assert (s.scope, s.arg) == ("stage", "build.pass2#3")
    with pytest.raises(ValueError):
        faultinject.parse("dead@stage:build.pass2#x")
    with pytest.raises(ValueError):
        faultinject.parse("oops@chunk:3")
    with pytest.raises(ValueError):
        faultinject.parse("oom@list:3")
    with pytest.raises(ValueError):
        faultinject.parse("oom@chunk:abc")


def test_faultinject_fires_once_per_spec():
    with faultinject.inject("oom@chunk:1"):
        faultinject.check(stage="s", chunk=0)          # no match
        with pytest.raises(faultinject.InjectedOOM):
            faultinject.check(stage="s", chunk=1)
        faultinject.check(stage="s", chunk=1)          # consumed
    faultinject.check(stage="s", chunk=1)              # plan cleared


def test_faultinject_proc_rpc_grammar():
    specs = faultinject.parse("dead@proc:2,slow@proc:1*3,drop@rpc:search")
    assert [(s.kind, s.scope, s.arg, s.remaining) for s in specs] == [
        ("dead", "proc", "2", 1), ("slow", "proc", "1", 3),
        ("drop", "rpc", "search", 1),
    ]
    with pytest.raises(ValueError):
        faultinject.parse("slow@chunk:1")     # slow is proc-only
    with pytest.raises(ValueError):
        faultinject.parse("drop@proc:1")      # drop is rpc-only
    with pytest.raises(ValueError):
        faultinject.parse("oom@proc:1")       # proc takes dead/slow only
    with pytest.raises(ValueError):
        faultinject.parse("dead@proc:x")      # proc rank must be int


def test_faultinject_proc_action_one_shot_and_repeat():
    with faultinject.inject("dead@proc:2,slow@proc:1*2"):
        assert faultinject.proc_action(0) is None
        assert faultinject.proc_action(1) == "slow"
        assert faultinject.proc_action(1) == "slow"
        assert faultinject.proc_action(1) is None      # count exhausted
        assert faultinject.proc_action(2) == "die"
        assert faultinject.proc_action(2) is None      # one-shot
    assert faultinject.proc_action(2) is None          # plan cleared


def test_faultinject_rpc_drop_consumed():
    with faultinject.inject("drop@rpc:search*2"):
        assert not faultinject.rpc_dropped("prepare")  # method-scoped
        assert faultinject.rpc_dropped("search")
        assert faultinject.rpc_dropped("search")
        assert not faultinject.rpc_dropped("search")   # count exhausted
    assert not faultinject.rpc_dropped("search")


def test_faultinject_proc_scopes_never_raise_from_check():
    # proc/rpc specs are queried, not raised: check() must ignore them
    with faultinject.inject("dead@proc:0,slow@proc:0,drop@rpc:search"):
        faultinject.check(stage="search", chunk=0)


def test_run_probe_clamped_to_deadline(monkeypatch):
    # a hanging probe (a backend hung in init) must not stall the
    # retry loop past deadline_s: run() clamps the probe wait to the
    # remaining deadline, and the probe timeout classifies dead_backend
    probe_waits = []

    def fake_alive(timeout_s=30.0):
        probe_waits.append(timeout_s)
        time.sleep(min(timeout_s, 5.0))   # hung probe honoring its bound
        return False

    monkeypatch.setattr(errors, "backend_alive", fake_alive)

    def dead():
        raise faultinject.InjectedDeadBackend("injected dead-backend")

    t0 = time.monotonic()
    with pytest.raises(errors.DeadBackendError):
        resilience.run(dead, retries=3, backoff_s=0.01, deadline_s=0.4,
                       probe_timeout_s=30.0)
    assert time.monotonic() - t0 < 2.0     # NOT the 30s probe default
    assert probe_waits and probe_waits[0] <= 0.4
    assert resilience.classify(errors.DeadBackendError("x")) == \
        resilience.DEAD_BACKEND


def test_faultinject_flap_and_delay_grammar():
    specs = faultinject.parse(
        "flap@proc:1#after:10*2,dead@proc:0#after:20,slow@proc:2*3")
    assert [(s.kind, s.arg, s.delay, s.remaining) for s in specs] == [
        ("flap", "1", 10, 2), ("dead", "0", 20, 1), ("slow", "2", 0, 3),
    ]
    # render round-trips the full grammar (the respawn rewrite depends
    # on it)
    again = faultinject.parse(",".join(s.render() for s in specs))
    assert [(s.kind, s.arg, s.delay, s.remaining) for s in again] == \
        [(s.kind, s.arg, s.delay, s.remaining) for s in specs]
    with pytest.raises(ValueError):
        faultinject.parse("flap@stage:x")        # flap is proc-only
    with pytest.raises(ValueError):
        faultinject.parse("flap@rpc:search")
    with pytest.raises(ValueError):
        faultinject.parse("dead@proc:1#later:3")  # only #after:N
    with pytest.raises(ValueError):
        faultinject.parse("dead@proc:1#after:x")
    with pytest.raises(ValueError):
        faultinject.parse("dead@proc:1#after:-2")


def test_faultinject_delayed_proc_action_arms_after_n():
    with faultinject.inject("dead@proc:0#after:2"):
        assert faultinject.proc_action(0) is None      # survives 1
        assert faultinject.proc_action(1) is None      # other rank
        assert faultinject.proc_action(0) is None      # survives 2
        assert faultinject.proc_action(0) == "die"     # armed
        assert faultinject.proc_action(0) is None      # consumed


def test_faultinject_flap_fires_per_count():
    with faultinject.inject("flap@proc:1*2"):
        assert faultinject.proc_action(1) == "die"
        assert faultinject.proc_action(1) == "die"
        assert faultinject.proc_action(1) is None      # budget spent


def test_faultinject_respawned_spec_rewrite():
    spec = "flap@proc:1#after:3*3,dead@proc:0#after:20,slow@proc:2*2"
    # rank 1's first respawn: one death charged, delay kept
    out = faultinject.respawned_spec(spec, rank=1, deaths=1)
    (flap,) = [s for s in faultinject.parse(out) if s.kind == "flap"]
    assert (flap.remaining, flap.delay) == (2, 3)
    # budget exhausted: the flap spec vanishes — the worker holds
    out = faultinject.respawned_spec(spec, rank=1, deaths=3)
    assert not any(s.kind == "flap" for s in faultinject.parse(out))
    # dead is permanent: the respawned incarnation dies at its FIRST
    # RPC (the #after delay modeled the first death only)
    out = faultinject.respawned_spec(spec, rank=0, deaths=1)
    (dead,) = [s for s in faultinject.parse(out) if s.kind == "dead"]
    assert (dead.remaining, dead.delay) == (1, 0)
    # other ranks' specs ride along verbatim
    (slow,) = [s for s in faultinject.parse(out) if s.kind == "slow"]
    assert (slow.arg, slow.remaining) == ("2", 2)
    assert faultinject.respawned_spec(None, 0, 1) is None
    assert faultinject.respawned_spec("flap@proc:0*1", 0, 1) is None


# ---------------------------------------------------------------------------
# full-jitter backoff (ISSUE 18)
# ---------------------------------------------------------------------------


def test_backoff_jitter_bounds_and_determinism():
    resilience.seed_jitter(42)
    try:
        a = [resilience.backoff_jitter_s(n, 0.1) for n in range(6)]
        resilience.seed_jitter(42)
        b = [resilience.backoff_jitter_s(n, 0.1) for n in range(6)]
        assert a == b                       # seeded => reproducible
        for n, s in enumerate(a):
            assert 0.0 <= s <= 0.1 * (2.0 ** n)
        # jitter=False returns the deterministic cap (legacy schedule)
        assert resilience.backoff_jitter_s(3, 0.1, jitter=False) == \
            pytest.approx(0.8)
        assert resilience.backoff_jitter_s(0, 0.0) == 0.0
    finally:
        resilience.seed_jitter(None)


def test_run_jittered_backoff_respects_deadline():
    # deadline math uses the UNJITTERED cap: a lucky small jitter draw
    # must not let the loop start an attempt it cannot afford
    resilience.seed_jitter(7)
    try:
        def always():
            raise resilience.TransientError("blip")

        t0 = time.monotonic()
        with pytest.raises(resilience.DeadlineExceededError):
            resilience.run(always, retries=50, backoff_s=0.2,
                           deadline_s=0.3)
        assert time.monotonic() - t0 < 5.0
        # and jitter=False restores the exact legacy sleep schedule
        calls = []

        def twice():
            calls.append(time.monotonic())
            if len(calls) < 3:
                raise resilience.TransientError("blip")
            return 9

        assert resilience.run(twice, retries=3, backoff_s=0.01,
                              jitter=False) == 9
        assert len(calls) == 3
    finally:
        resilience.seed_jitter(None)


def test_faultinject_env(monkeypatch):
    monkeypatch.setenv(faultinject.ENV_VAR, "transient@stage:probe")
    faultinject.clear()
    with pytest.raises(faultinject.InjectedTransient):
        faultinject.check(stage="probe")
    faultinject.check(stage="probe")                   # consumed
    monkeypatch.setenv(faultinject.ENV_VAR, "")
    faultinject.clear()
    assert not faultinject.active()


# ---------------------------------------------------------------------------
# tuning runtime budgets
# ---------------------------------------------------------------------------


def test_runtime_budget_records_min_and_clamps():
    assert tuning.runtime_budget("x") is None
    tuning.record_budget("x", 64)
    tuning.record_budget("x", 128)        # larger records keep the min
    assert tuning.runtime_budget("x") == 64
    assert tuning.budget("x", 512) == 64
    assert tuning.budget("x", 32) == 32   # never grows past the default
    tuning.reload()
    assert tuning.runtime_budget("x") is None


# ---------------------------------------------------------------------------
# checkpoint container
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    ck = resilience.StreamCheckpoint(str(tmp_path))
    assert ck.load() is None
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    ck.save("p", 2, {"rows": 3}, {"a": arr}, fingerprint={"k": 10})
    phase, step, meta, arrays = ck.load(fingerprint={"k": 10})
    assert (phase, step, meta) == ("p", 2, {"rows": 3})
    assert np.array_equal(arrays["a"], arr)
    # manifest-only peek agrees without touching the blob
    assert ck.peek(fingerprint={"k": 10}) == ("p", 2, {"rows": 3})
    with pytest.raises(checkpoint.CheckpointMismatchError):
        ck.load(fingerprint={"k": 11})
    with pytest.raises(checkpoint.CheckpointMismatchError):
        ck.peek(fingerprint={"k": 11})
    # later saves garbage-collect older blobs
    ck.save("p", 3, {"rows": 4}, {"a": arr}, fingerprint={"k": 10})
    blobs = [f for f in os.listdir(tmp_path) if f.endswith(".bin")]
    assert blobs == ["state-3.bin"]
    ck.clear()
    assert ck.load() is None


# ---------------------------------------------------------------------------
# streaming fault matrix (brute_force / ivf_flat / ivf_pq x chunk boundary)
# ---------------------------------------------------------------------------

_N, _D, _M, _K = 600, 24, 300, 10
_BATCH = 64                          # -> 5 chunks over 300 queries


class _BF:
    """brute_force adapter for the module.search(sp, index, q, k) shape."""

    @staticmethod
    def search(sp, index, batch, k):
        return brute_force.search(index, batch, k)


@pytest.fixture(scope="module")
def stream_data():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((_N, _D)).astype(np.float32)
    q = rng.standard_normal((_M, _D)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def stream_modules(stream_data):
    x, _ = stream_data
    flat = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4,
                             kmeans_trainset_fraction=1.0), x)
    pq = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4,
                           kmeans_trainset_fraction=1.0), x)
    return {
        "brute_force": (_BF, None, brute_force.build(x)),
        "ivf_flat": (ivf_flat,
                     ivf_flat.SearchParams(n_probes=8, query_group=8), flat),
        "ivf_pq": (ivf_pq,
                   ivf_pq.SearchParams(n_probes=8, query_group=8), pq),
    }


@pytest.mark.parametrize("algo", ["brute_force", "ivf_flat", "ivf_pq"])
@pytest.mark.parametrize("chunk", [0, 2, 4])
def test_oom_ladder_matches_uninjected(stream_modules, stream_data, algo,
                                       chunk):
    """Injected OOM at every chunk boundary converges via the halving
    ladder to results identical to the fault-free run."""
    mod, sp, index = stream_modules[algo]
    _, q = stream_data
    base_d, base_i = search_host_array(mod, sp, index, q, _K,
                                       batch_rows=_BATCH)
    with faultinject.inject(f"oom@chunk:{chunk}"):
        d, i = search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                                 backoff_s=0.001)
    assert np.array_equal(d, base_d)
    assert np.array_equal(i, base_i)
    assert tuning.runtime_budget("stream_batch_rows") == _BATCH // 2


@pytest.mark.parametrize("algo", ["brute_force", "ivf_flat", "ivf_pq"])
def test_dead_backend_mid_stage_recovers(stream_modules, stream_data, algo):
    """A dead backend mid-stage is probed (alive again on CPU: the
    injection is one-shot, like a bounced connection) and the batch retried;
    recovered results match the uninjected run."""
    mod, sp, index = stream_modules[algo]
    _, q = stream_data
    base_d, base_i = search_host_array(mod, sp, index, q, _K,
                                       batch_rows=_BATCH)
    with faultinject.inject("dead@chunk:1"):
        d, i = search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                                 backoff_s=0.001)
    assert np.array_equal(d, base_d)
    assert np.array_equal(i, base_i)


def test_oom_two_rungs_quarters_batch(stream_modules, stream_data):
    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    base_d, base_i = search_host_array(mod, sp, index, q, _K,
                                       batch_rows=_BATCH)
    with faultinject.inject("oom@chunk:1*3"):
        d, i = search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                                 backoff_s=0.001)
    assert np.array_equal(d, base_d)
    assert np.array_equal(i, base_i)
    # every re-dispatch of chunk 1 re-arms the spec: 64, 32, 16 all
    # struck, the 8-row rung survived
    assert tuning.runtime_budget("stream_batch_rows") == _BATCH // 8


def test_oom_at_min_rows_propagates(stream_modules, stream_data):
    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    # more strikes than the ladder has rungs for one 64-row batch
    with faultinject.inject("oom@chunk:0*50"):
        with pytest.raises(faultinject.InjectedOOM):
            search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                              backoff_s=0.001)


def test_transient_retry_bitwise(stream_modules, stream_data):
    mod, sp, index = stream_modules["ivf_flat"]
    _, q = stream_data
    base_d, base_i = search_host_array(mod, sp, index, q, _K,
                                       batch_rows=_BATCH)
    with faultinject.inject("transient@chunk:0,transient@chunk:3"):
        d, i = search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                                 backoff_s=0.001)
    assert np.array_equal(d, base_d)
    assert np.array_equal(i, base_i)


# ---------------------------------------------------------------------------
# checkpointed streaming search
# ---------------------------------------------------------------------------


def test_search_ckpt_resume_bitwise(stream_modules, stream_data, tmp_path):
    """A job killed at an arbitrary chunk resumes to bitwise-identical
    results, skipping the chunks the checkpoint already covers."""
    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    base_d, base_i = search_host_array(mod, sp, index, q, _K,
                                       batch_rows=_BATCH)
    ckdir = str(tmp_path / "ck")
    with faultinject.inject("dead@chunk:3"):
        with pytest.raises(faultinject.InjectedDeadBackend):
            search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                              checkpoint_dir=ckdir, checkpoint_every=1,
                              retries=0)
    # the manifest proves 3 chunks (192 rows) completed before the kill
    import json

    manifest = json.load(open(os.path.join(ckdir, "manifest.json")))
    assert manifest["meta"]["rows_done"] == 3 * _BATCH
    d, i = search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                             checkpoint_dir=ckdir, resume=True)
    assert np.array_equal(d, base_d)
    assert np.array_equal(i, base_i)


def test_search_resume_other_batch_size_bitwise(stream_modules, stream_data,
                                                tmp_path):
    """Host-array resume restarts AT the completed-row mark (start_row),
    so a different batch_rows still yields bitwise-identical output —
    per-query searches are row-independent."""
    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    base_d, base_i = search_host_array(mod, sp, index, q, _K,
                                       batch_rows=_BATCH)
    ckdir = str(tmp_path / "ck2")
    with faultinject.inject("dead@chunk:2"):
        with pytest.raises(faultinject.InjectedDeadBackend):
            search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                              checkpoint_dir=ckdir, checkpoint_every=1,
                              retries=0)
    d, i = search_host_array(mod, sp, index, q, _K, batch_rows=48,
                             checkpoint_dir=ckdir, resume=True)
    assert np.array_equal(d, base_d)
    assert np.array_equal(i, base_i)


def test_search_stream_rejects_misaligned_iterator(stream_modules,
                                                   stream_data, tmp_path):
    """An iterator that cannot seek (the file path) re-produces batches
    from offset 0 at a DIFFERENT size than the checkpoint was written at
    — search_stream refuses rather than splice misaligned rows."""
    from raft_tpu.neighbors.stream import search_stream
    from raft_tpu.utils.batch import BatchLoadIterator

    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    ckdir = str(tmp_path / "ck3")

    def fn(batch):
        return mod.search(sp, index, batch, _K)

    with faultinject.inject("dead@chunk:2"):
        with pytest.raises(faultinject.InjectedDeadBackend):
            search_stream(fn, BatchLoadIterator(q, _BATCH, pad_to_full=True),
                          q.shape[0], _K, checkpoint_dir=ckdir,
                          checkpoint_every=1, retries=0)
    with pytest.raises(ValueError, match="resume misalignment"):
        search_stream(fn, BatchLoadIterator(q, 48, pad_to_full=True),
                      q.shape[0], _K, checkpoint_dir=ckdir, resume=True)


# ---------------------------------------------------------------------------
# checkpointed build (ivf_pq.build_streamed)
# ---------------------------------------------------------------------------

_BN, _BD = 512, 16


def _build_batches(x, bs=64):
    def make():
        for s in range(0, x.shape[0], bs):
            yield jnp.asarray(x[s:s + bs])
    return make


def _assert_index_bitwise(a, b):
    for f in ("codes", "indices", "list_sizes", "rec_norms", "centers",
              "centers_rot", "rotation", "pq_centers"):
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f


@pytest.fixture(scope="module")
def build_setup():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((_BN, _BD)).astype(np.float32)
    params = ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4,
                                kmeans_trainset_fraction=1.0)
    base = ivf_pq.build_streamed(params, _build_batches(x), _BN, _BD,
                                 trainset=x)
    return x, params, base


@pytest.mark.parametrize("fault", ["dead@stage:build.pass1",
                                   "dead@chunk:3",
                                   "dead@stage:build.pass2#3"])
def test_build_stream_kill_resume_bitwise(build_setup, tmp_path, fault):
    """build_streamed killed mid-pass-1 (chunk:3 also lands there —
    pass-1 chunks come first) or mid-pass-2 (the stage#chunk spec, which
    exercises the donated-accumulator restore) resumes from the
    per-chunk checkpoint to a bitwise-identical index (quantizers are
    restored, never retrained)."""
    x, params, base = build_setup
    ckdir = str(tmp_path / "bck")
    with faultinject.inject(fault):
        with pytest.raises(faultinject.InjectedDeadBackend):
            ivf_pq.build_streamed(params, _build_batches(x), _BN, _BD,
                                  trainset=x, checkpoint_dir=ckdir,
                                  checkpoint_every=1)
    got = ivf_pq.build_streamed(params, _build_batches(x), _BN, _BD,
                                trainset=x, checkpoint_dir=ckdir,
                                checkpoint_every=1, resume=True)
    _assert_index_bitwise(base, got)


def test_build_stream_resume_rejects_other_config(build_setup, tmp_path):
    x, params, _ = build_setup
    ckdir = str(tmp_path / "bck2")
    with faultinject.inject("dead@chunk:2"):
        with pytest.raises(faultinject.InjectedDeadBackend):
            ivf_pq.build_streamed(params, _build_batches(x), _BN, _BD,
                                  trainset=x, checkpoint_dir=ckdir,
                                  checkpoint_every=1)
    import dataclasses

    other = dataclasses.replace(params, n_lists=16)
    with pytest.raises(checkpoint.CheckpointMismatchError):
        ivf_pq.build_streamed(other, _build_batches(x), _BN, _BD,
                              trainset=x, checkpoint_dir=ckdir,
                              checkpoint_every=1, resume=True)


# ---------------------------------------------------------------------------
# cooperative cancellation through the streaming loops
# ---------------------------------------------------------------------------


def test_cancel_stops_search(stream_modules, stream_data):
    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    tok = Interruptible()
    tok.cancel()
    with pytest.raises(InterruptedException):
        search_host_array(mod, sp, index, q, _K, batch_rows=_BATCH,
                          token=tok)


def test_cancel_from_other_thread_stops_search(stream_modules, stream_data):
    mod, sp, index = stream_modules["brute_force"]
    _, q = stream_data
    tok = Interruptible()
    started = threading.Event()

    class _Slow:
        @staticmethod
        def search(sp_, index_, batch, k):
            started.set()
            time.sleep(0.05)
            return mod.search(sp_, index_, batch, k)

    result = {}

    def work():
        try:
            search_host_array(_Slow, sp, index, q, _K, batch_rows=32,
                              token=tok)
            result["out"] = "finished"
        except InterruptedException:
            result["out"] = "interrupted"

    t = threading.Thread(target=work)
    t.start()
    started.wait(10.0)
    tok.cancel()
    t.join(30.0)
    assert result.get("out") == "interrupted"


def test_cancel_stops_build():
    rng = np.random.default_rng(12)
    x = rng.standard_normal((_BN, _BD)).astype(np.float32)
    params = ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=4,
                                kmeans_trainset_fraction=1.0)
    tok = Interruptible()
    tok.cancel()
    with pytest.raises(InterruptedException):
        ivf_pq.build_streamed(params, _build_batches(x), _BN, _BD,
                              trainset=x, token=tok)


# ---------------------------------------------------------------------------
# CAGRA transient-buffer ladder
# ---------------------------------------------------------------------------


def test_shrinking_blocks_tail_oom_keeps_budget():
    """An OOM on a short tail block retries the tail at half size but
    must NOT shrink the process-wide budget to half-of-a-few-rows."""
    calls = []

    def fn(start, rows):
        calls.append((start, rows))
        return jnp.arange(start, start + rows)

    # blocks of 64 over 70 rows -> full block [0,64), tail [64,70);
    # strike the tail (chunk 1) with OOM
    with faultinject.inject("oom@chunk:1"):
        parts = list(degrade.run_shrinking_blocks(
            fn, 70, 64, budget_name="tail_test", stage="tail"))
    got = np.concatenate([np.asarray(p) for p in parts])
    assert np.array_equal(got, np.arange(70))
    # tail retried at 3 rows, but no budget recorded (full block never failed)
    assert tuning.runtime_budget("tail_test") is None
    # a FULL block failing must still record
    with faultinject.inject("oom@chunk:0"):
        parts = list(degrade.run_shrinking_blocks(
            fn, 70, 64, budget_name="tail_test2", stage="tail"))
    got = np.concatenate([np.asarray(p) for p in parts])
    assert np.array_equal(got, np.arange(70))
    assert tuning.runtime_budget("tail_test2") == 32


def test_cagra_detour_ladder_bitwise():
    from raft_tpu.neighbors import cagra

    rng = np.random.default_rng(13)
    graph = rng.integers(0, 200, (200, 8)).astype(np.int32)
    base = np.asarray(cagra._detour_counts(graph, 64, nodes_per_call=64))
    tuning.reload()
    with faultinject.inject("oom@chunk:1"):
        got = np.asarray(cagra._detour_counts(graph, 64, nodes_per_call=64))
    assert np.array_equal(base, got)
    assert tuning.runtime_budget("cagra_detour_rows") == 32


# ---------------------------------------------------------------------------
# graceful shard degradation (dropout at each rank) + auto-padding
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shard_data():
    rng = np.random.default_rng(17)
    x = rng.standard_normal((96, 16)).astype(np.float32)
    q = rng.standard_normal((7, 16)).astype(np.float32)
    return x, q


@pytest.mark.parametrize("rank", list(range(8)))
def test_sharded_knn_dropout_each_rank(shard_data, eight_device_mesh, rank):
    """One injected dead shard -> partial_ok results with coverage
    (S-1)/S, exactly equal to exact KNN over the surviving shards."""
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    k, rows = 5, x.shape[0] // 8
    with faultinject.inject(f"shard@rank:{rank}"):
        d, i, cov = sharded_knn(q, x, k, eight_device_mesh, partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(7 / 8)
    keep = np.ones(x.shape[0], bool)
    keep[rank * rows:(rank + 1) * rows] = False
    ids_map = np.nonzero(keep)[0]
    _, want = naive_knn(q, x[keep], k)
    assert np.array_equal(np.asarray(i), ids_map[want])


def test_sharded_knn_dropout_without_partial_ok_raises(shard_data,
                                                       eight_device_mesh):
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    with faultinject.inject("shard@rank:3"):
        with pytest.raises(resilience.ShardDropoutError):
            sharded_knn(q, x, 5, eight_device_mesh)


def test_sharded_knn_real_nan_shard_masked(shard_data, eight_device_mesh):
    """A real fault signature (NaN rows in one shard) is detected and
    masked the same way an injected dropout is — no injection involved."""
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    rows = x.shape[0] // 8
    x_bad = x.copy()
    x_bad[2 * rows:3 * rows] = np.nan
    d, i, cov = sharded_knn(q, x_bad, 5, eight_device_mesh, partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(7 / 8)
    keep = np.ones(x.shape[0], bool)
    keep[2 * rows:3 * rows] = False
    ids_map = np.nonzero(keep)[0]
    _, want = naive_knn(q, x[keep], 5)
    assert np.array_equal(np.asarray(i), ids_map[want])


def test_sharded_knn_nan_query_row_confined(shard_data, eight_device_mesh):
    """Queries are replicated, so one NaN QUERY row poisons that row on
    every shard — masking is per row: the other queries' results survive
    untouched and only the bad row degrades."""
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    d0, i0 = sharded_knn(q, x, 5, eight_device_mesh)
    q_bad = q.copy()
    q_bad[3, 0] = np.nan
    d, i, cov = sharded_knn(q_bad, x, 5, eight_device_mesh, partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(1 - 1 / q.shape[0])
    i = np.asarray(i)
    assert np.all(i[3] == -1)
    good = np.ones(q.shape[0], bool)
    good[3] = False
    assert np.array_equal(i[good], np.asarray(i0)[good])


def test_build_stream_resume_rejects_other_batch_shape(build_setup,
                                                       tmp_path):
    """Index-based batch skipping is only sound when the resumed stream
    yields the same shapes — a different make_batches must be refused,
    not silently spliced."""
    x, params, _ = build_setup
    ckdir = str(tmp_path / "bck3")
    with faultinject.inject("dead@chunk:3"):
        with pytest.raises(faultinject.InjectedDeadBackend):
            ivf_pq.build_streamed(params, _build_batches(x), _BN, _BD,
                                  trainset=x, checkpoint_dir=ckdir,
                                  checkpoint_every=1)
    with pytest.raises(ValueError, match="resume misalignment"):
        ivf_pq.build_streamed(params, _build_batches(x, bs=32), _BN, _BD,
                              trainset=x, checkpoint_dir=ckdir,
                              checkpoint_every=1, resume=True)


def test_sharded_knn_autopads_nondivisible(shard_data, eight_device_mesh):
    """Satellite: n not divisible by the mesh axis no longer raises —
    sentinel rows pad the tail shard and never surface in the top-k."""
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    x = x[:91]                               # 91 % 8 != 0
    d, i = sharded_knn(q, x, 5, eight_device_mesh)
    rd, ri = naive_knn(q, x, 5)
    assert np.array_equal(np.asarray(i), ri)
    assert np.all(np.asarray(i) >= 0)


def test_sharded_knn_autopad_with_dropout(shard_data, eight_device_mesh):
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    x = x[:91]
    rows = -(-91 // 8)                       # padded shard rows
    with faultinject.inject("shard@rank:7"):
        d, i, cov = sharded_knn(q, x, 5, eight_device_mesh, partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(7 / 8)
    keep = np.ones(91, bool)
    keep[7 * rows:] = False                  # rank 7 holds the tail + pad
    ids_map = np.nonzero(keep)[0]
    _, want = naive_knn(q, x[keep], 5)
    assert np.array_equal(np.asarray(i), ids_map[want])


@pytest.mark.parametrize("rank", [0, 4, 7])
def test_sharded_ivf_flat_dropout(rng, eight_device_mesh, rank):
    """List-sharded IVF-Flat with one dead shard: coverage drops and no
    returned id comes from the dead shard's lists."""
    from raft_tpu.comms import sharded_ivf_search

    n, m, d, k = 1024, 16, 32, 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4,
                             kmeans_trainset_fraction=1.0), x)
    sp = ivf_flat.SearchParams(n_probes=16, query_group=8,
                               local_recall_target=1.0)
    with faultinject.inject(f"shard@rank:{rank}"):
        dist, idx, cov = sharded_ivf_search(sp, index, q, k,
                                            eight_device_mesh,
                                            partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(7 / 8)
    local = 16 // 8
    dead_ids = set(
        np.asarray(index.indices)[rank * local:(rank + 1) * local].ravel()
    ) - {-1}
    got = set(np.asarray(idx).ravel()) - {-1}
    assert not (got & dead_ids)
    assert np.all(np.isfinite(np.asarray(dist)[np.asarray(idx) >= 0]))


@pytest.mark.parametrize("rank", [1, 6])
def test_sharded_ivf_pq_dropout(rng, eight_device_mesh, rank):
    from raft_tpu.comms import sharded_ivf_pq_search

    n, m, d, k = 1024, 16, 32, 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    index = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, pq_dim=16, pq_bits=8,
                           kmeans_n_iters=4,
                           kmeans_trainset_fraction=1.0), x)
    sp = ivf_pq.SearchParams(n_probes=16, query_group=8,
                             local_recall_target=1.0)
    with faultinject.inject(f"shard@rank:{rank}"):
        dist, idx, cov = sharded_ivf_pq_search(sp, index, q, k,
                                               eight_device_mesh,
                                               partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(7 / 8)
    local = 16 // 8
    dead_ids = set(
        np.asarray(index.indices)[rank * local:(rank + 1) * local].ravel()
    ) - {-1}
    got = set(np.asarray(idx).ravel()) - {-1}
    assert not (got & dead_ids)


def test_sharded_partial_ok_full_coverage(shard_data, eight_device_mesh):
    """partial_ok with NO fault returns coverage 1.0 and the same answer
    as the plain call."""
    from raft_tpu.comms import sharded_knn

    x, q = shard_data
    d0, i0 = sharded_knn(q, x, 5, eight_device_mesh)
    d1, i1, cov = sharded_knn(q, x, 5, eight_device_mesh, partial_ok=True)
    assert float(np.asarray(cov)) == 1.0
    assert np.array_equal(np.asarray(i0), np.asarray(i1))


# ---------------------------------------------------------------------------
# batch iterator hooks
# ---------------------------------------------------------------------------


def test_batch_iterator_live_shrink_and_start_row():
    from raft_tpu.utils.batch import BatchLoadIterator

    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    it = BatchLoadIterator(x, 8)
    seen = []
    for off, batch in it:
        seen.append((off, batch.shape[0]))
        if off == 0:
            it.set_batch_rows(4)
    # the one-slot prefetch means the shrink lands one batch later —
    # batch (8, 8) was already staged when (0, 8) was consumed
    assert seen == [(0, 8), (8, 8), (16, 4)]

    it2 = BatchLoadIterator(x, 8, start_row=8)
    assert [off for off, _ in it2] == [8, 16]
    assert len(it2) == 2


# ---------------------------------------------------------------------------
# graft-race regression (ISSUE 7): one-shot spec consumption discipline
# ---------------------------------------------------------------------------


def test_faultinject_one_shot_exact_under_concurrency():
    """A `*K` spec fires exactly K times across racing consumers: plan
    resolution and the `remaining` decrement share ONE critical
    section (the old fetch-then-relock consumed off a detached list)."""
    from raft_tpu.resilience import faultinject

    faultinject.install("slow@proc:0*5")
    try:
        hits = []
        barrier = threading.Barrier(8)

        def consume():
            barrier.wait()
            for _ in range(4):
                if faultinject.proc_action(0) == "slow":
                    hits.append(1)

        ts = [threading.Thread(target=consume, daemon=True)
              for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(hits) == 5, len(hits)
    finally:
        faultinject.clear()


def test_faultinject_clear_wins_over_stale_plan():
    """After clear(), a consumer must see the LIVE (empty) plan — not a
    list it fetched before the swap."""
    from raft_tpu.resilience import faultinject

    faultinject.install("dead@proc:0*1")
    faultinject.clear()
    assert faultinject.proc_action(0) is None
    faultinject.install("drop@rpc:search*1")
    faultinject.install(None)
    assert not faultinject.rpc_dropped("search")
