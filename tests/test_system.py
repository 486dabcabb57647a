"""End-to-end system behaviour: the full GenASM read-mapping service with
checkpoint/restart fault tolerance, and accuracy vs the DP gold standard
(the paper's §4.10.2 analysis in miniature)."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointManager
from repro.core import dp_baseline, mapper, minimizer_index, oracle
from repro.core.genasm_tb import cigar_score
from repro.dist.fault import RestartableLoop, WorkQueue
from repro.genomics import encode, pipeline, simulate


def _setup(n_reads=24, seed=0):
    ref = simulate.random_reference(6000, seed=seed)
    idx = minimizer_index.build_reference_index(ref, w=8, k=12)
    rs = simulate.simulate_reads(ref, n_reads=n_reads, read_len=120,
                                 profile=simulate.ILLUMINA, seed=seed + 1)
    return ref, idx, rs


def test_mapping_service_with_workqueue():
    """Stateless batch mapping through the lease-based scheduler."""
    ref, idx, rs = _setup()
    batches = list(pipeline.ReadBatches(rs.reads, batch=8, cap=128))
    q = WorkQueue(len(batches), lease_s=60)
    done = {}
    while not q.finished:
        b = q.claim()
        if b is None:
            break
        _, arr, lens = batches[b]
        res = mapper.map_batch(idx, jnp.asarray(arr), jnp.asarray(lens),
                               p_cap=192, filter_bits=128, filter_k=16,
                               minimizer_w=8, minimizer_k=12)
        done[b] = np.asarray(res.position)
        q.complete(b)
    assert len(done) == len(batches)
    pos = np.concatenate([done[b] for b in sorted(done)])
    ok = np.abs(pos[: len(rs.true_pos)] - rs.true_pos) <= 16
    assert ok.mean() >= 0.75


def test_checkpoint_restart_resumes(tmp_path):
    """Kill the loop mid-run; restart resumes from the latest checkpoint."""
    mgr = CheckpointManager(tmp_path, keep=3)
    state = {"cursor": jnp.int32(0)}

    calls = []

    def step_fn(st, step):
        calls.append(step)
        if len(calls) == 5 and not getattr(step_fn, "resumed", False):
            raise RuntimeError("simulated node failure")
        return {"cursor": st["cursor"] + 1}

    loop = RestartableLoop(mgr, save_every=2)
    try:
        loop.run(state, step_fn, n_steps=10)
        assert False, "should have crashed"
    except RuntimeError:
        pass
    mgr.wait()
    assert mgr.latest_step() is not None
    step_fn.resumed = True
    final = loop.run(state, step_fn, n_steps=10)
    assert int(final["cursor"]) == 10


def test_genasm_score_parity_vs_dp():
    """Paper §4.10.2: GenASM alignment scores track the DP gold standard."""
    ref, idx, rs = _setup(n_reads=16, seed=3)
    reads, lens = encode.batch_reads(rs.reads, 128)
    res = mapper.map_batch(idx, jnp.asarray(reads), jnp.asarray(lens),
                           p_cap=192, filter_bits=128, filter_k=16,
                           minimizer_w=8, minimizer_k=12)
    pos = np.asarray(res.position)
    close = 0
    total = 0
    for i in range(16):
        if pos[i] < 0:
            continue
        total += 1
        g_score = int(cigar_score(jnp.asarray(np.asarray(res.ops)[i]),
                                  jnp.int32(int(np.asarray(res.n_ops)[i]))))
        region = np.full((192 + 128,), 4, np.int8)
        chunk = ref[pos[i]: pos[i] + 192 + 128]
        region[: len(chunk)] = chunk
        pbuf = np.full((192,), 0, np.int8)
        pbuf[: lens[i]] = reads[i, : lens[i]]
        dp = int(dp_baseline.affine_align_score(
            jnp.asarray(region), jnp.asarray(pbuf), jnp.int32(int(lens[i])),
            jnp.int32(len(chunk))))
        if dp != 0 and abs(g_score - dp) <= max(8, abs(dp) * 0.1):
            close += 1
    assert total >= 12
    assert close / total >= 0.8, f"{close}/{total}"


def test_compile_cache_dir_follows_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise a fixed
    directory inside the checkout."""
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None  # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
