"""paddle.distributed namespace parity + dataset/reader/cost_model stack.

Reference analog: python/paddle/distributed/__init__.py __all__ (38 names),
python/paddle/reader/decorator.py tests (reader decorators), dataset
reader-creator contract, cost_model/cost_model.py.
"""

import numpy as np
import pytest

import paddle_tpu as paddle


# ----------------------------------------------------- namespace parity

def test_distributed_all_38():
    import paddle_tpu.distributed as d
    assert len(d.__all__) == 38
    missing = [n for n in d.__all__ if not hasattr(d, n)]
    assert not missing, missing


def test_launch_is_callable_and_module_runs():
    import paddle_tpu.distributed as d
    assert callable(d.launch)


def test_parallel_mode_exported():
    import paddle_tpu.distributed as d
    assert hasattr(d.ParallelMode, "DATA_PARALLEL")


# ----------------------------------------------------------- entry_attr

def test_entry_attr_to_attr_strings():
    import paddle_tpu.distributed as d
    assert d.ProbabilityEntry(0.5)._to_attr() == "probability_entry:0.5"
    assert d.CountFilterEntry(3)._to_attr() == "count_filter_entry:3"
    assert d.ShowClickEntry("show", "click")._to_attr() == \
        "show_click_entry:show:click"


def test_entry_attr_validation():
    import paddle_tpu.distributed as d
    with pytest.raises(ValueError):
        d.ProbabilityEntry(0)
    with pytest.raises(ValueError):
        d.ProbabilityEntry("x")
    with pytest.raises(ValueError):
        d.CountFilterEntry(-1)
    with pytest.raises(ValueError):
        d.ShowClickEntry("s", 3)


def test_count_filter_entry_admits_after_n():
    from paddle_tpu.distributed.entry_attr import CountFilterEntry
    e = CountFilterEntry(3)
    assert not e.admit(7, None)
    assert not e.admit(7, None)
    assert e.admit(7, None)          # third touch admits
    assert e.admit(7, None)


# ------------------------------------------------------- fleet datasets

def _write_filelist(tmp_path, n_files=2, lines_per=8):
    paths = []
    rng = np.random.default_rng(0)
    for i in range(n_files):
        p = tmp_path / f"part-{i}.txt"
        with open(p, "w") as f:
            for _ in range(lines_per):
                feats = " ".join(f"{v:.3f}" for v in rng.random(4))
                f.write(f"{feats} {int(rng.integers(0, 2))}\n")
        paths.append(str(p))
    return paths


def test_in_memory_dataset(tmp_path):
    from paddle_tpu.distributed import InMemoryDataset
    ds = InMemoryDataset()
    ds.init(batch_size=4)
    ds.set_filelist(_write_filelist(tmp_path))
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 16
    ds.local_shuffle(seed=0)
    batches = list(ds.batches())
    assert len(batches) == 4
    x, y = batches[0]
    assert x.shape == (4, 4) and y.shape == (4,)
    ds.release_memory()
    assert ds.get_memory_data_size() == 0


def test_queue_dataset_streams_and_rejects_shuffle(tmp_path):
    from paddle_tpu.distributed import QueueDataset
    ds = QueueDataset()
    ds.init(batch_size=8)
    ds.set_filelist(_write_filelist(tmp_path))
    batches = list(ds.batches())
    assert len(batches) == 2
    with pytest.raises(NotImplementedError):
        ds.local_shuffle()


def test_in_memory_dataset_custom_parser(tmp_path):
    from paddle_tpu.distributed import InMemoryDataset
    p = tmp_path / "csv.txt"
    with open(p, "w") as f:
        f.write("1,2\n3,4\n")
    ds = InMemoryDataset()
    ds.init(batch_size=2, pipe_command=lambda line: np.asarray(
        [float(v) for v in line.strip().split(",")], np.float32))
    ds.set_filelist([str(p)])
    ds.load_into_memory()
    (batch,) = list(ds.batches())
    np.testing.assert_array_equal(batch, [[1, 2], [3, 4]])


# ------------------------------------------------------------- reader

def test_reader_decorators_compose():
    import paddle_tpu.reader as reader

    def r():
        return iter(range(10))

    assert list(reader.firstn(r, 3)()) == [0, 1, 2]
    assert list(reader.chain(r, r)()) == list(range(10)) * 2
    assert sorted(reader.shuffle(r, 4)()) == list(range(10))
    assert list(reader.buffered(r, 2)()) == list(range(10))
    assert list(reader.map_readers(lambda a, b: a + b, r, r)()) == \
        [2 * i for i in range(10)]
    cached = reader.cache(r)
    assert list(cached()) == list(range(10))
    assert list(cached()) == list(range(10))


def test_reader_compose_alignment():
    import paddle_tpu.reader as reader

    def r5():
        return iter(range(5))

    def r3():
        return iter(range(3))

    out = list(reader.compose(r5, r5)())
    assert out[0] == (0, 0)
    with pytest.raises(reader.ComposeNotAligned):
        list(reader.compose(r5, r3)())
    # check_alignment=False truncates instead
    assert len(list(reader.compose(r5, r3, check_alignment=False)())) == 3


def test_reader_xmap_and_multiprocess():
    import paddle_tpu.reader as reader

    def r():
        return iter(range(20))

    out = sorted(reader.xmap_readers(lambda x: x * 2, r, 3, 8)())
    assert out == [2 * i for i in range(20)]
    out2 = sorted(reader.xmap_readers(lambda x: x + 1, r, 2, 4, order=True)())
    assert out2 == [i + 1 for i in range(20)]
    mp = reader.multiprocess_reader([r, r], queue_size=16)
    assert sorted(mp()) == sorted(list(range(20)) * 2)


# -------------------------------------------------------- paddle.dataset

def test_dataset_mnist_reader():
    import paddle_tpu.dataset as dataset
    sample = next(dataset.mnist.train()())
    img, label = sample
    assert img.shape == (784,)
    assert img.min() >= -1.0 and img.max() <= 1.0
    assert 0 <= label <= 9


def test_dataset_cifar_uci_imdb_imikolov():
    import paddle_tpu.dataset as dataset
    img, label = next(dataset.cifar.train10()())
    assert img.shape == (3072,)
    feats, price = next(dataset.uci_housing.train()())
    assert feats.shape == (13,)
    toks, lab = next(dataset.imdb.train(dataset.imdb.word_dict())())
    assert isinstance(toks, list) and lab in (0, 1)
    gram = next(dataset.imikolov.train(n=5)())
    assert len(gram) == 5


def test_dataset_common_split_and_cluster(tmp_path, monkeypatch):
    import paddle_tpu.dataset.common as common
    # restored at teardown: a worker's later test files spawn children
    # that find `paddle_tpu` through the working directory
    monkeypatch.chdir(tmp_path)

    def r():
        return iter(range(10))

    common.split(r, 4, suffix=str(tmp_path / "chunk-%05d.pickle"))
    rd = common.cluster_files_reader(str(tmp_path / "chunk-*.pickle"), 1, 0)
    assert sorted(rd()) == list(range(10))


# ------------------------------------------------------------ cost_model

def test_cost_model():
    from paddle_tpu.cost_model import CostModel
    cm = CostModel()
    startup, main = cm.build_program()
    cost = cm.profile_measure(startup, main, device="cpu")
    assert cost["time"] > 0
    data = cm.static_cost_data()
    assert any(d["op"] == "matmul" for d in data)
    t = cm.get_static_op_time("matmul")
    assert t["op_time"] > 0
    back = cm.get_static_op_time("matmul", forward=False)
    assert back["op_time"] >= t["op_time"]
    with pytest.raises(ValueError):
        cm.get_static_op_time(None)


# -------------------------------------------------- gloo control plane

def test_gloo_single_rank_roundtrip():
    import paddle_tpu.distributed as d
    port = 29771
    d.gloo_init_parallel_env(0, 1, f"127.0.0.1:{port}")
    d.gloo_barrier()
    d.gloo_release()
    # double release is harmless
    d.gloo_release()


def test_sparse_table_entry_admission():
    """CountFilterEntry gates PS sparse-table materialization: rows appear
    only after N touches; un-admitted pulls are zeros."""
    from paddle_tpu.distributed.ps import SparseTable
    from paddle_tpu.distributed.entry_attr import CountFilterEntry
    t = SparseTable("emb", 4, entry=CountFilterEntry(2))
    first = t.pull([7])
    np.testing.assert_array_equal(first, np.zeros((1, 4), np.float32))
    assert 7 not in t.rows
    second = t.pull([7])                 # second touch admits
    assert 7 in t.rows
    assert np.abs(second).sum() > 0


def test_partial_p2p_warns_once_about_control_plane():
    """partial_send/recv ride the host-mediated path: a once-per-process
    RuntimeWarning must point users at the compiled ppermute data plane."""
    import warnings
    import paddle_tpu.distributed as d
    from paddle_tpu.distributed import collective as coll
    coll._partial_p2p_warned = False      # reset the once-latch
    t = paddle.to_tensor(np.arange(8, dtype=np.float32))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d.partial_send(t, dst=0, nranks=2, rank_id=0)
        d.partial_send(t, dst=0, nranks=2, rank_id=1)
    msgs = [x for x in w if issubclass(x.category, RuntimeWarning)
            and "ppermute" in str(x.message)]
    assert len(msgs) == 1                 # fired exactly once


def test_communication_stream_package():
    """paddle.distributed.communication.stream variants (reference:
    distributed/communication/stream/) — use_calc_stream accepted, results
    match the eager collectives at world 1."""
    import paddle_tpu.distributed as d
    assert hasattr(d, "stream") and hasattr(d, "communication")
    t = paddle.to_tensor(np.asarray([1.0, 2.0], np.float32))
    task = d.stream.all_reduce(t, sync_op=False, use_calc_stream=True)
    task.wait()
    np.testing.assert_allclose(np.asarray(t._value), [1.0, 2.0])
    out = []
    d.stream.all_gather(out, t)
    assert len(out) == 1
    dst = paddle.to_tensor(np.zeros(2, np.float32))
    d.stream.alltoall_single(dst, t)
    np.testing.assert_allclose(np.asarray(dst._value), [1.0, 2.0])
    for name in ("all_gather", "all_reduce", "alltoall", "alltoall_single",
                 "broadcast", "reduce", "reduce_scatter", "recv", "scatter",
                 "send"):
        assert hasattr(d.stream, name), name


# -------------------------------------------------------- fleet surface

def test_fleet_surface_39():
    """paddle.distributed.fleet exposes the reference __all__ + singleton
    bindings (reference fleet/__init__.py:39-104)."""
    from paddle_tpu.distributed import fleet
    names = ["CommunicateTopology", "UserDefinedRoleMaker",
             "PaddleCloudRoleMaker", "Role", "UtilBase",
             "HybridCommunicateGroup", "MultiSlotDataGenerator",
             "MultiSlotStringDataGenerator", "Fleet", "DistributedStrategy",
             "init", "is_first_worker", "worker_index", "worker_num",
             "is_worker", "worker_endpoints", "server_num", "server_index",
             "server_endpoints", "is_server", "util", "barrier_worker",
             "init_worker", "init_server", "run_server", "stop_worker",
             "distributed_optimizer", "save_inference_model",
             "save_persistables", "distributed_model", "state_dict",
             "set_state_dict", "shrink", "get_lr", "set_lr", "minimize",
             "DatasetBase", "InMemoryDataset", "QueueDataset"]
    missing = [n for n in names if not hasattr(fleet, n)]
    assert not missing, missing


def test_role_makers(monkeypatch):
    from paddle_tpu.distributed.fleet import (UserDefinedRoleMaker,
                                              PaddleCloudRoleMaker, Role)
    rm = UserDefinedRoleMaker(current_id=1, role=Role.WORKER, worker_num=4)
    assert rm._worker_index() == 1 and rm._worker_num() == 4
    assert rm._is_worker() and not rm._is_server()
    rm2 = UserDefinedRoleMaker(
        current_id=0, role=Role.SERVER,
        worker_endpoints=["127.0.0.1:6170"],
        server_endpoints=["127.0.0.1:6270", "127.0.0.1:6271"])
    assert rm2._is_server() and rm2._server_num() == 2
    monkeypatch.setenv("TRAINING_ROLE", "TRAINER")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
    monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS",
                       "127.0.0.1:6170,127.0.0.1:6171,127.0.0.1:6172")
    cloud = PaddleCloudRoleMaker()
    assert cloud._worker_index() == 2 and cloud._worker_num() == 3


def test_util_base_file_shard():
    from paddle_tpu.distributed.fleet import (UtilBase,
                                              UserDefinedRoleMaker, Role)
    files = [f"f{i}" for i in range(7)]
    shards = []
    for rank in range(3):
        u = UtilBase()
        u._set_role_maker(UserDefinedRoleMaker(
            current_id=rank, role=Role.WORKER, worker_num=3))
        shards.append(u.get_file_shard(files))
    # contiguous, disjoint, covering; earlier ranks carry the remainder
    assert [len(s) for s in shards] == [3, 2, 2]
    assert sum(shards, []) == files
    # all_reduce/all_gather degenerate correctly at world 1
    u = UtilBase()
    np.testing.assert_allclose(u.all_reduce(np.asarray([1.0, 2.0])),
                               [1.0, 2.0])
    assert u.all_gather(5) == [5]


def test_multislot_data_generator():
    from paddle_tpu.distributed.fleet import MultiSlotDataGenerator

    class G(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def gen():
                toks = [int(v) for v in line.split()]
                yield [("words", toks[:-1]), ("label", [toks[-1]])]
            return gen

    g = G()
    out = g.run_from_memory(["1 2 3 1", "4 5 6 0"])
    assert out == ["3 1 2 3 1 1\n", "3 4 5 6 1 0\n"]
    # inconsistent slot name must raise
    class Bad(MultiSlotDataGenerator):
        def __init__(self):
            super().__init__()
            self.n = 0
        def generate_sample(self, line):
            def gen():
                self.n += 1
                name = "words" if self.n == 1 else "other"
                yield [(name, [1])]
            return gen
    with pytest.raises(ValueError):
        Bad().run_from_memory(["a", "b"])


def test_data_generator_feeds_fleet_dataset(tmp_path):
    """The generator's MultiSlot lines parse back through InMemoryDataset
    with a matching parser — the end-to-end ingest contract."""
    from paddle_tpu.distributed.fleet import (MultiSlotDataGenerator,
                                              InMemoryDataset)

    class G(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def gen():
                vals = [float(v) for v in line.split()]
                yield [("feat", vals[:-1]), ("label", [int(vals[-1])])]
            return gen

    g = G()
    lines = g.run_from_memory(["0.5 0.25 1", "0.125 0.75 0"])
    p = tmp_path / "part-0.txt"
    with open(p, "w") as f:
        f.writelines(lines)

    def parse(line):
        toks = line.split()
        n_feat = int(toks[0])
        feats = np.asarray([float(v) for v in toks[1:1 + n_feat]],
                           np.float32)
        label = np.asarray(int(float(toks[2 + n_feat])), np.int64)
        return feats, label

    ds = InMemoryDataset()
    ds.init(batch_size=2, pipe_command=parse)
    ds.set_filelist([str(p)])
    ds.load_into_memory()
    (x, y), = list(ds.batches())
    np.testing.assert_allclose(x, [[0.5, 0.25], [0.125, 0.75]])
    np.testing.assert_array_equal(y, [1, 0])


def test_fleet_singleton_state_passthrough():
    from paddle_tpu.distributed import fleet
    import paddle_tpu.nn as nn
    paddle.seed(0)
    m = nn.Linear(4, 2)
    fleet.init(is_collective=True)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.SGD(learning_rate=0.5, parameters=m.parameters()))
    assert fleet.get_lr() == 0.5
    sd = fleet.state_dict()
    assert isinstance(sd, dict)
    assert fleet.is_first_worker() and fleet.is_worker()
    assert not fleet.is_server()
    assert fleet.worker_num() >= 1


def test_passes_framework():
    """paddle.distributed.passes (reference pass_base.py:131 new_pass,
    :311 PassManager): functional delegates + compiler-owned no-ops."""
    from paddle_tpu.distributed.passes import (new_pass, PassManager,
                                               PassContext)
    import paddle_tpu.nn as nn
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    p_amp = new_pass("auto_parallel_amp",
                     {"model": model, "optimizer": opt})
    p_gm = new_pass("auto_parallel_gradient_merge_pass",
                    {"optimizer": opt, "k_steps": 2})
    p_fuse = new_pass("fuse_all_reduce")
    pm = PassManager([p_amp, p_gm, p_fuse])
    ctx = pm.apply()
    assert len(ctx.applied_passes) == 3
    assert opt._multi_precision is True
    import jax.numpy as jnp
    assert model[0].weight._value.dtype == jnp.bfloat16   # O2 cast
    from paddle_tpu.distributed.fleet.meta_optimizers import \
        GradientMergeOptimizer
    assert isinstance(ctx.attrs["optimizer"], GradientMergeOptimizer)
    assert ctx.attrs["compiler_owned"] == ["fuse_all_reduce"]
    assert pm.names == ["auto_parallel_amp",
                        "auto_parallel_gradient_merge_pass",
                        "fuse_all_reduce"]
    with pytest.raises(ValueError, match="not registered"):
        new_pass("no_such_pass")
    with pytest.raises(ValueError, match="needs"):
        new_pass("auto_parallel_recompute").apply(None, None, PassContext())


def test_passes_write_through_wrappers():
    """AMP/sharding passes must write on the INNERMOST optimizer when
    handed a fleet wrapper (review regression: wrapper __getattr__ makes
    reads transparent but writes land on the wrapper)."""
    from paddle_tpu.distributed.passes import new_pass
    from paddle_tpu.distributed.fleet.meta_optimizers import \
        GradientMergeOptimizer
    import paddle_tpu.nn as nn
    paddle.seed(0)
    m = nn.Linear(8, 4)
    inner = paddle.optimizer.AdamW(learning_rate=1e-2,
                                   parameters=m.parameters())
    wrapped = GradientMergeOptimizer(inner, k_steps=2)
    new_pass("auto_parallel_amp",
             {"model": m, "optimizer": wrapped}).apply(None, None)
    assert inner._multi_precision is True       # inner, not wrapper dict
    assert "_multi_precision" not in wrapped.__dict__
    # fp16 variant is registered too
    p = new_pass("auto_parallel_fp16", {"model": m})
    assert p.name == "auto_parallel_fp16"


def test_pass_manager_conflict_hooks():
    from paddle_tpu.distributed.passes import (PassBase, PassManager,
                                               register_pass, new_pass)

    @register_pass("_test_conflicting")
    class Conflicting(PassBase):
        def _check_conflict(self, other):
            return other.name != "fuse_all_reduce"

        def _apply_impl(self, mains, startups, ctx):
            pass

    a = new_pass("fuse_all_reduce")
    b = new_pass("_test_conflicting")
    pm = PassManager([a, b])                    # auto-solve drops b
    assert pm.names == ["fuse_all_reduce"]
    with pytest.raises(ValueError, match="conflicts"):
        PassManager([a, b], auto_solve_conflict=False)


# ----------------------------------------- secondary distributed modules

def test_moe_gate_utils():
    from paddle_tpu.distributed.models.moe import (
        _number_count, _assign_pos, _random_routing, _limit_by_capacity,
        _prune_gate_by_capacity)
    # number_count: reference docstring example
    numbers = paddle.to_tensor(np.asarray([[0, 2], [0, 2]], np.int32))
    nc = _number_count(numbers, 6)
    np.testing.assert_array_equal(np.asarray(nc._value), [2, 0, 2, 0, 0, 0])
    # assign_pos: tokens ordered expert-by-expert, stable within expert
    gate = paddle.to_tensor(np.asarray([1, 0, 1, 0], np.int64))
    cum = paddle.to_tensor(np.asarray([2, 4], np.int64))
    pos = _assign_pos(gate, cum)
    np.testing.assert_array_equal(np.asarray(pos._value), [1, 3, 0, 2])
    # random_routing: 2*value < prob drops the 2nd choice
    idx = paddle.to_tensor(np.asarray([[0, 1], [2, 3]], np.int64))
    val = paddle.to_tensor(np.asarray([[0.9, 0.05], [0.8, 0.4]],
                                      np.float32))
    prob = paddle.to_tensor(np.asarray([0.5, 0.5], np.float32))
    out = _random_routing(idx, val, prob)
    np.testing.assert_array_equal(np.asarray(out._value),
                                  [[0, -1], [2, 3]])
    # limit_by_capacity: worker 0 served first
    ec = paddle.to_tensor(np.asarray([3, 1, 4, 2], np.int64))  # 2 workers
    cap = paddle.to_tensor(np.asarray([4, 2], np.int64))       # x 2 experts
    lim = _limit_by_capacity(ec, cap, n_worker=2)
    np.testing.assert_array_equal(np.asarray(lim._value), [3, 1, 1, 1])
    # prune_gate: budget [1,1] kills the second token per expert
    g = paddle.to_tensor(np.asarray([0, 0, 1, 1], np.int64))
    budget = paddle.to_tensor(np.asarray([1, 1], np.int64))
    pruned = _prune_gate_by_capacity(g, budget, 2, 1)
    np.testing.assert_array_equal(np.asarray(pruned._value),
                                  [0, -1, 1, -1])


def test_global_scatter_gather_world1_roundtrip():
    import warnings
    from paddle_tpu.distributed.utils import global_scatter, global_gather
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(4, 3))
    local = paddle.to_tensor(np.asarray([2, 2], np.int64))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        y = global_scatter(x, local, local)
        np.testing.assert_allclose(np.asarray(y._value),
                                   np.asarray(x._value))
        back = global_gather(y, local, local)
    np.testing.assert_allclose(np.asarray(back._value),
                               np.asarray(x._value))


def test_distributed_metric_auc():
    from paddle_tpu.distributed.metric import init_metric, print_auc
    from paddle_tpu.distributed.metric.metrics import update_metric
    ptr = init_metric(name="auc", bucket_size=4095)
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 2, 512)
    # informative predictions -> AUC well above 0.5
    preds = np.clip(labels * 0.6 + rng.random(512) * 0.4, 0, 1)
    update_metric("auc", preds, labels)
    auc = print_auc(ptr)
    assert 0.7 < auc <= 1.0


def test_cloud_utils_cluster(monkeypatch):
    from paddle_tpu.distributed import cloud_utils
    monkeypatch.setenv("PADDLE_TRAINERS", "10.0.0.1,10.0.0.2")
    monkeypatch.setenv("POD_IP", "10.0.0.2")
    monkeypatch.setenv("TRAINER_PORTS", "6170,6171")
    cluster, pod = cloud_utils.get_cloud_cluster()
    assert cluster.world_size() == 4
    assert pod.ip == "10.0.0.2" and pod.rank == 1
    assert cluster.trainers_endpoints()[0] == "10.0.0.1:6170"
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "3")
    assert cloud_utils.get_trainers_num() == 3


def test_static_sparse_embedding_with_entry():
    import paddle_tpu.static.nn as snn
    from paddle_tpu.distributed.entry_attr import CountFilterEntry
    ids = paddle.to_tensor(np.asarray([[7, 9]], np.int64))
    e1 = snn.sparse_embedding(ids, size=[100, 8], name="se_test",
                              entry=CountFilterEntry(2))
    assert np.abs(np.asarray(e1._value)).sum() == 0.0   # gated
    e2 = snn.sparse_embedding(ids, size=[100, 8], name="se_test")
    assert tuple(e2.shape) == (1, 2, 8)
    assert np.abs(np.asarray(e2._value)).sum() > 0       # admitted
    # padding_idx rows stay zero
    ids3 = paddle.to_tensor(np.asarray([[0, 7]], np.int64))
    e3 = snn.sparse_embedding(ids3, size=[100, 8], name="se_test",
                              padding_idx=0)
    assert np.abs(np.asarray(e3._value)[0, 0]).sum() == 0.0


def test_sparse_embedding_identity_and_dim_guards():
    import paddle_tpu.static.nn as snn
    ids = paddle.to_tensor(np.asarray([[1]], np.int64))
    with pytest.raises(ValueError, match="stable identity"):
        snn.sparse_embedding(ids, size=[10, 4])
    snn.sparse_embedding(ids, size=[10, 4], name="se_dim_guard")
    with pytest.raises(ValueError, match="already exists"):
        snn.sparse_embedding(ids, size=[10, 8], name="se_dim_guard")


def test_cloud_utils_unknown_pod_ip_raises(monkeypatch):
    from paddle_tpu.distributed import cloud_utils
    monkeypatch.setenv("PADDLE_TRAINERS", "10.0.0.1,10.0.0.2")
    monkeypatch.setenv("POD_IP", "192.168.1.9")
    monkeypatch.setenv("TRAINER_PORTS", "6170")
    with pytest.raises(ValueError, match="not in the trainer list"):
        cloud_utils.get_cloud_cluster()


def test_assign_pos_skips_pruned_ids():
    """Pruned (-1) gate ids must not be dispatched (review regression)."""
    from paddle_tpu.distributed.models.moe import _assign_pos
    gate = paddle.to_tensor(np.asarray([-1, 0, -1, 1], np.int64))
    cum = paddle.to_tensor(np.asarray([1, 2], np.int64))
    pos = _assign_pos(gate, cum)
    np.testing.assert_array_equal(np.asarray(pos._value), [1, 3])


def test_metric_top_bucket_mass_counts():
    """Predictions in the top histogram bucket must contribute to the
    global AUC exactly as to the local one (review regression)."""
    from paddle_tpu.distributed.metric import init_metric, print_auc
    from paddle_tpu.distributed.metric.metrics import (update_metric,
                                                       get_metric)
    ptr = init_metric(name="auc_top")
    labels = np.asarray([0, 1, 0, 1])
    update_metric("auc_top", np.ones(4, np.float32), labels)  # all ties
    local = float(get_metric("auc_top").accumulate())
    glob = print_auc(ptr, name="auc_top")
    np.testing.assert_allclose(glob, local)
    assert abs(glob - 0.5) < 1e-6


def test_cloud_utils_multinode_needs_pod_ip(monkeypatch):
    from paddle_tpu.distributed import cloud_utils
    monkeypatch.setenv("PADDLE_TRAINERS", "10.0.0.1,10.0.0.2")
    monkeypatch.delenv("POD_IP", raising=False)
    monkeypatch.setenv("TRAINER_PORTS", "6170")
    with pytest.raises(ValueError, match="POD_IP"):
        cloud_utils.get_cloud_cluster()
