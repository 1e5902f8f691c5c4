"""Real multi-process distributed test — the raft-dask-analog bootstrap
(raft_tpu.bootstrap.init_multihost) exercised with TWO OS processes over jax.distributed
(gloo on CPU), running a psum and a sharded KNN across the process mesh.

This is the multi-host path the reference covers with its NCCL/MPI comms
tests (cpp/test/core/device_resources_manager.cu + raft-dask test_comms);
single-process CPU-mesh tests elsewhere cover the collectives themselves.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    from raft_tpu.bootstrap import init_multihost
    init_multihost(coordinator_address=f"127.0.0.1:{port}",
                   num_processes=nproc, process_id=pid)

    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices())
    assert len(devs) == nproc, f"expected {nproc} global devices, got {devs}"
    mesh = Mesh(devs, ("shard",))

    # collective sanity: psum across hosts
    def f(x):
        return jax.lax.psum(x, "shard")

    y = jax.jit(shard_map(f, mesh=mesh, in_specs=P("shard"), out_specs=P(),
                          check_vma=False))(
        jnp.ones((nproc,), jnp.float32)
    )
    assert float(y[0]) == nproc

    # sharded brute-force KNN over the cross-process mesh
    from raft_tpu.comms import sharded_knn

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64 * nproc, 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    d, i = sharded_knn(q, x, 4, mesh)
    # oracle on every host (same data everywhere)
    full = np.asarray(x)
    dist = ((np.asarray(q)[:, None, :] - full[None, :, :]) ** 2).sum(-1)
    want = np.argsort(dist, axis=1)[:, :4]
    got = np.asarray(i)
    recall = np.mean([len(set(got[r]) & set(want[r])) / 4 for r in range(8)])
    assert recall > 0.99, recall

    # session registry (raft-dask Comms.init/local_handle analog,
    # raft_dask/common/comms.py:173,248,269): two concurrent sessions on
    # this worker, collectives routed through each session's handle
    from raft_tpu.comms import CommsSession, get_comm_state, session_handle

    s1 = CommsSession(mesh).init()
    s2 = CommsSession(mesh).init()
    assert s1.sessionId != s2.sessionId
    assert set(get_comm_state(None)) >= {s1.sessionId, s2.sessionId}
    for s, mult in ((s1, 1.0), (s2, 2.0)):
        h = session_handle(s.sessionId)
        assert h is not None and h.mesh is mesh

        def g(x, _c=h.comms):
            return _c.allreduce(x)

        z = jax.jit(shard_map(g, mesh=h.mesh, in_specs=P("shard"),
                              out_specs=P(), check_vma=False))(
            jnp.full((nproc,), mult, jnp.float32)
        )
        assert float(z[0]) == nproc * mult, (s.sessionId, float(z[0]))
    s1.destroy()
    assert session_handle(s2.sessionId) is not None
    assert get_comm_state(None).get(s1.sessionId) is None
    s2.destroy()
    print(f"proc{pid} OK", flush=True)
    """
)


def _launch_once(worker, env):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out.decode())
    finally:
        for p in procs:  # never leak hung rendezvous workers
            if p.poll() is None:
                p.kill()
    return procs, outs


def test_two_process_multihost(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    )
    env.pop("XLA_FLAGS", None)  # no virtual device splitting in workers
    # the bind-then-close port pick can race other processes: retry once
    # with a fresh port before declaring failure
    for attempt in (0, 1):
        procs, outs = _launch_once(worker, env)
        if all(p.returncode == 0 for p in procs) or attempt == 1:
            break
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc{pid} failed:\n{out[-2000:]}"
        assert f"proc{pid} OK" in out
