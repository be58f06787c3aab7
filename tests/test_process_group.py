"""Multi-process eager collectives over the store-backed ProcessGroup.

Mirrors the reference's per-collective API tests
(test/collective/collective_allreduce_api.py etc., run through
test_communication_api_base spawning real trainer processes): the parent
spawns world_size real Python processes; each runs every collective
against NumPy expectations and reports pass/fail through its exit code.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORLD = 3


def _worker():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])

    # N worker processes cannot share a chip: pin them to the CPU
    # whatever environment the suite was started from
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    assert dist.get_rank() == rank
    assert dist.get_world_size() == world

    def arr(r, shape=(4, 3), dtype=np.float32):
        return (np.arange(np.prod(shape), dtype=dtype).reshape(shape)
                + 100.0 * r)

    # all_reduce sum / max / avg (in-place, process_group.h AllReduce)
    for op, expect in [
        (dist.ReduceOp.SUM, sum(arr(r) for r in range(world))),
        (dist.ReduceOp.MAX, arr(world - 1)),
        (dist.ReduceOp.AVG, sum(arr(r) for r in range(world)) / world),
    ]:
        t = paddle.to_tensor(arr(rank))
        dist.all_reduce(t, op=op)
        np.testing.assert_allclose(t.numpy(), expect, rtol=1e-6)

    # all_gather
    outs = []
    dist.all_gather(outs, paddle.to_tensor(arr(rank)))
    assert len(outs) == world
    for r, o in enumerate(outs):
        np.testing.assert_array_equal(o.numpy(), arr(r))

    # broadcast from src=1
    t = paddle.to_tensor(arr(rank))
    dist.broadcast(t, src=1)
    np.testing.assert_array_equal(t.numpy(), arr(1))

    # reduce to dst=2
    t = paddle.to_tensor(arr(rank))
    dist.reduce(t, dst=2, op=dist.ReduceOp.SUM)
    if rank == 2:
        np.testing.assert_allclose(
            t.numpy(), sum(arr(r) for r in range(world)), rtol=1e-6)

    # reduce_scatter: rank r gets sum over ranks of their r-th part
    parts = [paddle.to_tensor(arr(rank) + 10.0 * i) for i in range(world)]
    t = paddle.to_tensor(np.zeros((4, 3), np.float32))
    dist.reduce_scatter(t, parts)
    expect = sum(arr(r) + 10.0 * rank for r in range(world))
    np.testing.assert_allclose(t.numpy(), expect, rtol=1e-6)

    # scatter from src=0
    t = paddle.to_tensor(np.zeros((4, 3), np.float32))
    slist = [paddle.to_tensor(arr(0) + 7.0 * i) for i in range(world)] \
        if rank == 0 else None
    dist.scatter(t, slist, src=0)
    np.testing.assert_array_equal(t.numpy(), arr(0) + 7.0 * rank)

    # gather to dst=1
    glist = []
    dist.gather(paddle.to_tensor(arr(rank)), glist, dst=1)
    if rank == 1:
        assert len(glist) == world
        for r, o in enumerate(glist):
            np.testing.assert_array_equal(o.numpy(), arr(r))

    # alltoall
    outs = []
    ins = [paddle.to_tensor(arr(rank) + 1000.0 * i) for i in range(world)]
    dist.alltoall(outs, ins)
    for r, o in enumerate(outs):
        np.testing.assert_array_equal(o.numpy(), arr(r) + 1000.0 * rank)

    # send/recv ring: rank -> rank+1 (bfloat16 exercises the wire format)
    import ml_dtypes
    payload = arr(rank, dtype=np.float32).astype(ml_dtypes.bfloat16)
    nxt, prv = (rank + 1) % world, (rank - 1) % world
    if rank % 2 == 0:
        dist.send(paddle.to_tensor(payload), dst=nxt)
        t = paddle.to_tensor(np.zeros((4, 3), np.float32))
        dist.recv(t, src=prv)
    else:
        t = paddle.to_tensor(np.zeros((4, 3), np.float32))
        dist.recv(t, src=prv)
        dist.send(paddle.to_tensor(payload), dst=nxt)
    np.testing.assert_array_equal(
        t.numpy().astype(np.float32),
        arr(prv, dtype=np.float32).astype(ml_dtypes.bfloat16)
        .astype(np.float32))

    # barrier is reusable (regression: round counter, store.py barrier)
    for _ in range(3):
        dist.barrier()

    # objects
    objs = []
    dist.all_gather_object(objs, {"rank": rank})
    assert [o["rank"] for o in objs] == list(range(world))
    lst = [{"cfg": rank}]
    dist.broadcast_object_list(lst, src=2)
    assert lst == [{"cfg": 2}]

    # subgroup [0, 2]: must be created on every rank, used by members
    g = dist.new_group([0, 2])
    if rank in (0, 2):
        t = paddle.to_tensor(arr(rank))
        dist.all_reduce(t, group=g)
        np.testing.assert_allclose(t.numpy(), arr(0) + arr(2), rtol=1e-6)
        # subgroup barrier counts to the GROUP size, not world size
        dist.barrier(group=g)
        # a non-member src must raise immediately, not hang on the store
        try:
            dist.broadcast(paddle.to_tensor(arr(rank)), src=1, group=g)
            raise AssertionError("expected ValueError for non-member src")
        except ValueError:
            pass

    dist.barrier()
    print(f"WORKER-{rank}-OK", flush=True)


def test_collectives_multiprocess(tmp_path):
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    procs = []
    for rank in range(WORLD):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(WORLD),
            # hostname (not IPv4 literal) exercises getaddrinfo resolution
            "MASTER_ADDR": "localhost",
            "MASTER_PORT": str(port),
            "JAX_PLATFORMS": "cpu",
            "PT_PG_WORKER": "1",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for rank, p in enumerate(procs):
            out, _ = p.communicate(timeout=240)
            outs.append((rank, p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rank, rc, out in outs:
        assert rc == 0, f"rank {rank} failed (rc={rc}):\n{out}"
        assert f"WORKER-{rank}-OK" in out


if __name__ == "__main__" and os.environ.get("PT_PG_WORKER") == "1":
    _worker()
