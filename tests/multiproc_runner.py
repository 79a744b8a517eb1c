"""One rank of the multi-process collective harness.

Launched by tests/test_multiproc_collective.py via subprocess.Popen with
PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_MASTER set (reference
analog: the trainer scripts TestDistBase forks,
unittests/test_dist_base.py:1150 + collective/collective_sendrecv_api.py).

Each rank: TCPStore rendezvous -> jax.distributed.initialize -> runs every
eager collective across REAL processes and asserts the cross-process result.
"""
import os
import sys


def main():
    # the harness runs on the CPU whatever the machine holds
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    assert world == int(os.environ["PADDLE_TRAINERS_NUM"]), \
        (world, os.environ["PADDLE_TRAINERS_NUM"])
    assert jax.process_count() == world

    def t(arr):
        return paddle.to_tensor(np.asarray(arr, np.float32))

    # --- all_reduce: sum of (rank+1) over ranks -----------------------------
    x = t([float(rank + 1)] * 4)
    dist.all_reduce(x)
    expect = sum(r + 1 for r in range(world))
    np.testing.assert_allclose(np.asarray(x._value), expect)

    # --- broadcast from rank 0 ---------------------------------------------
    b = t([rank * 10.0, rank * 10.0])
    dist.broadcast(b, src=0)
    np.testing.assert_allclose(np.asarray(b._value), 0.0)

    # --- all_gather ---------------------------------------------------------
    gathered = []
    dist.all_gather(gathered, t([float(rank)] * 3))
    assert len(gathered) == world
    for r in range(world):
        np.testing.assert_allclose(np.asarray(gathered[r]._value), float(r))

    # --- send/recv: ring r -> (r+1) % world ---------------------------------
    # EVERY rank sends first — the host-mediated p2p must not deadlock on
    # crossing sends (an SPMD-collective p2p would)
    payload = t([float(100 + rank)] * 2)
    inbox = t([0.0, 0.0])
    src = (rank - 1) % world
    dst = (rank + 1) % world
    dist.send(payload, dst=dst)
    dist.recv(inbox, src=src)
    np.testing.assert_allclose(np.asarray(inbox._value), float(100 + src))
    # second round (reversed ring) proves sequence keys don't collide
    dist.send(payload, dst=src)
    dist.recv(inbox, src=dst)
    np.testing.assert_allclose(np.asarray(inbox._value), float(100 + dst))

    # --- partial_send/partial_recv: exchange one half-slice ------------------
    big = t([float(rank)] * 8)
    slot = t([0.0] * 8)
    dist.partial_send(big, dst=dst, nranks=2, rank_id=1)
    dist.partial_recv(slot, src=src, nranks=2, rank_id=1)
    got = np.asarray(slot._value)
    np.testing.assert_allclose(got[:4], 0.0)       # untouched half
    np.testing.assert_allclose(got[4:], float(src))

    # --- batch_isend_irecv ---------------------------------------------------
    # every rank lists irecv FIRST (the canonical ring-exchange order):
    # the batch must hoist the sends, or both ends would deadlock
    outbox = t([float(rank * 2)] * 2)
    inbox2 = t([0.0, 0.0])
    tasks = dist.batch_isend_irecv([
        dist.P2POp(dist.irecv, inbox2, src),
        dist.P2POp(dist.isend, outbox, dst)])
    for tk in tasks:
        tk.wait()
    np.testing.assert_allclose(np.asarray(inbox2._value), float(src * 2))

    # --- reduce_scatter -----------------------------------------------------
    parts = [t([float(rank + 1)] * 2) for _ in range(world)]
    out = t([0.0, 0.0])
    dist.reduce_scatter(out, parts)
    np.testing.assert_allclose(np.asarray(out._value), expect)

    # --- alltoall -----------------------------------------------------------
    ins = [t([float(rank * world + j)] * 2) for j in range(world)]
    outs = []
    dist.alltoall(ins, outs)
    for i in range(world):
        np.testing.assert_allclose(np.asarray(outs[i]._value),
                                   float(i * world + rank))

    # --- alltoall_single ----------------------------------------------------
    flat = t([float(rank * world + j) for j in range(world)])
    single_out = t([0.0] * world)
    dist.alltoall_single(flat, single_out)
    np.testing.assert_allclose(
        np.asarray(single_out._value),
        [float(i * world + rank) for i in range(world)])

    # --- scatter from rank 0 ------------------------------------------------
    chunk = t([0.0, 0.0])
    if rank == 0:
        dist.scatter(chunk, [t([float(7 + r)] * 2) for r in range(world)],
                     src=0)
    else:
        dist.scatter(chunk, src=0)
    np.testing.assert_allclose(np.asarray(chunk._value), float(7 + rank))

    # --- all_gather_object (pickled, ragged) --------------------------------
    objs = []
    dist.all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
    assert [o["rank"] for o in objs] == list(range(world))
    assert all(objs[r]["tag"] == "x" * (r + 1) for r in range(world))

    # --- LocalSGD over a REAL dp axis ----------------------------------------
    # each rank trains on DIFFERENT data for k_steps, then the averaging
    # step must leave every rank with IDENTICAL parameters (reference
    # localsgd_optimizer.py semantics)
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.fleet.meta_optimizers import (
        LocalSGDOptimizer, DGCMomentum)
    paddle.seed(0)                       # same init on every rank
    m = nn.Linear(4, 2)
    opt = LocalSGDOptimizer(
        paddle.optimizer.SGD(learning_rate=1e-2,
                             parameters=m.parameters()), k_steps=3)
    rng = np.random.default_rng(100 + rank)     # different data per rank
    for i in range(3):                   # step 3 triggers the averaging
        x_ = paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32))
        y_ = paddle.to_tensor(rng.normal(size=(8, 2)).astype(np.float32))
        loss = ((m(x_) - y_) * (m(x_) - y_)).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    mine = np.asarray(m.weight._value)
    gathered = []
    dist.all_gather_object(gathered, mine)
    for other in gathered:
        np.testing.assert_allclose(other, mine, rtol=1e-6, atol=1e-7)

    # --- DGC over a REAL dp axis ---------------------------------------------
    # identical data + identical init => the compressed all-reduced grads
    # are identical, so params must track exactly across ranks
    paddle.seed(1)
    m2 = nn.Linear(4, 2)
    opt2 = DGCMomentum(
        paddle.optimizer.Momentum(learning_rate=1e-2, momentum=0.9,
                                  parameters=m2.parameters()),
        sparsity=(0.5,))
    rng2 = np.random.default_rng(7)      # SAME data on every rank
    for i in range(3):
        x_ = paddle.to_tensor(rng2.normal(size=(8, 4)).astype(np.float32))
        y_ = paddle.to_tensor(rng2.normal(size=(8, 2)).astype(np.float32))
        loss = ((m2(x_) - y_) * (m2(x_) - y_)).mean()
        loss.backward()
        opt2.step()
        opt2.clear_grad()
    mine2 = np.asarray(m2.weight._value)
    gathered2 = []
    dist.all_gather_object(gathered2, mine2)
    for other in gathered2:
        np.testing.assert_allclose(other, mine2, rtol=1e-6, atol=1e-7)

    # --- global_scatter / global_gather (MoE token exchange) -----------------
    # 1 expert per card: rank r sends `r+1` tokens to every card; the
    # gather must return exactly the original tokens
    if world <= 4:
        import warnings as _w
        from paddle_tpu.distributed.utils import (global_scatter,
                                                  global_gather)
        n_send = world * (rank + 1)
        x_moe = t(np.arange(n_send * 2, dtype=np.float32)
                  .reshape(n_send, 2) + 100 * rank)
        local_count = t(np.asarray([rank + 1] * world, np.int64))
        # rank r receives (c+1) tokens from each card c
        global_count = t(np.asarray([c + 1 for c in range(world)],
                                    np.int64))
        with _w.catch_warnings():
            _w.simplefilter("ignore")
            recv = global_scatter(x_moe, local_count, global_count)
            assert recv._value.shape[0] == sum(
                c + 1 for c in range(world)), recv._value.shape
            back = global_gather(recv, local_count, global_count)
        np.testing.assert_allclose(np.asarray(back._value),
                                   np.asarray(x_moe._value))

    # --- barrier + store round-trip -----------------------------------------
    dist.barrier()
    store = dist.env.get_store()
    assert store is not None
    store.set(f"mark/{rank}", str(rank))
    store.barrier("marks")
    for r in range(world):
        assert store.get(f"mark/{r}").decode() == str(r)

    print(f"RANK {rank} OK", flush=True)


if __name__ == "__main__":
    main()
