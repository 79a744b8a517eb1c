"""The system under test, as the loops see it: paddle_tpu's GPT through
`jit.TrainStep` (one chip, or `shard_gpt` over a data x model mesh) and
through `serving.LLMEngine`. This is the only module of the benchmark that
imports the program. Sizes and constructor arguments come from the
configuration and traffic files; the construction follows chip_smoke.py."""
from __future__ import annotations

import contextlib

PREFIX = "gpt."     # the program's parameter names are the reference's + this


def enable_compile_cache():
    """One fixed directory inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says)."""
    from paddle_tpu.framework.compile_cache import enable_compile_cache
    return enable_compile_cache()


def _model_config(cfg):
    from paddle_tpu.incubate.models import GPTConfig
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"],
        max_position_embeddings=cfg["n_positions"],
        hidden_dropout_prob=cfg["resid_pdrop"],
        attention_probs_dropout_prob=cfg["attn_pdrop"],
        initializer_range=cfg["initializer_range"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"])


def _new_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTForCausalLM
    paddle.seed(0)          # every value is replaced by the seeded weights
    model = GPTForCausalLM(_model_config(cfg))
    model.bfloat16()
    return model


def _named(model):
    return {name[len(PREFIX):]: p for name, p in model.named_parameters()}


def _seeded_weights(model, make_weights):
    """`make_weights(shardings or None)` -> {reference name: array}, each
    leaf made where the model keeps that parameter."""
    shardings = {k: p._value.sharding for k, p in _named(model).items()}
    spread = any(len(s.device_set) > 1 for s in shardings.values())
    return make_weights(shardings if spread else None)


def _set_weights(model, make_weights):
    named = _named(model)
    weights = _seeded_weights(model, make_weights)
    if set(weights) != set(named):
        raise ValueError("the reference's parameters are not the program's: "
                         f"{sorted(set(weights) ^ set(named))[:6]}")
    for k, p in named.items():
        if tuple(p._value.shape) != tuple(weights[k].shape):
            raise ValueError(f"{k}: program {tuple(p._value.shape)}, "
                             f"reference {tuple(weights[k].shape)}")
        p._value = weights[k]


class Trainer:
    """`TrainStep` with its model and AdamW, on one device or a mesh."""

    def __init__(self, cfg, traffic, make_weights, devices):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.models import GPTPretrainingCriterion
        from paddle_tpu.jit import TrainStep
        self._paddle = paddle
        self.mesh = None
        dims = traffic.get("mesh")
        self._exit = contextlib.ExitStack()
        if dims:
            from paddle_tpu.distributed.mesh import (build_mesh,
                                                     set_global_mesh)
            self.mesh = build_mesh(dp=dims["data"], pp=1, sharding=1, sep=1,
                                   mp=dims["model"], devices=devices)
            set_global_mesh(self.mesh)
            self._exit.callback(set_global_mesh, None)
        self.model = _new_model(cfg)
        if dims:
            from paddle_tpu.incubate.models import shard_gpt
            shard_gpt(self.model, self.mesh)
        _set_weights(self.model, make_weights)
        self._make_weights = make_weights
        opt = cfg["optimizer"]
        self.opt = paddle.optimizer.AdamW(
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"],
            parameters=self.model.parameters(), multi_precision=True)
        criterion = GPTPretrainingCriterion()
        self.train_step = TrainStep(
            self.model, lambda logits, y: criterion(logits, y), self.opt,
            donate=traffic["donate"])
        self._params = [p for p in self.model.parameters()
                        if not p.stop_gradient]
        if dims:
            from paddle_tpu.distributed.fleet.sharding_opt import \
                shard_optimizer_states
            self.opt._create_accumulators(self._params)
            shard_optimizer_states(self.opt)

    def close(self):
        self._exit.close()

    def batch_sharding(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(("data", "sharding"), None))

    def _tensors(self, ids, labels):
        T = self._paddle.Tensor
        return T(ids, stop_gradient=True), T(labels, stop_gradient=True)

    def step(self, ids, labels):
        """One step; returns the loss as a device array, not waited for."""
        return self.train_step(*self._tensors(ids, labels))._value

    def program_bytes(self, ids, labels):
        """What the compiler reserves for the step on each device."""
        analysis = self.train_step.lower(
            *self._tensors(ids, labels)).compile().memory_analysis()
        return {k: int(getattr(analysis, f"{k}_size_in_bytes"))
                for k in ("argument", "output", "alias", "temp")}

    def _slot(self, name):
        self.opt._create_accumulators(self._params)
        slots = self.opt._accumulators[name]
        return {k: slots[p.name] for k, p in _named(self.model).items()}

    def master_params(self):
        """The float32 parameters the optimizer keeps (the bf16 ones are
        their rounding)."""
        return self._slot("master_weight")

    def initial_params(self):
        """The parameters the first step started from, made again from
        the seed (a kept copy would not fit beside the largest cell)."""
        return _seeded_weights(self.model, self._make_weights)

    def first_moment(self):
        return self._slot("moment1")

    def state_arrays(self):
        return [p._value for p in self._params] + [
            v for slots in self.opt._accumulators.values()
            for v in slots.values() if hasattr(v, "addressable_shards")]


def build_trainer(cfg, traffic, make_weights, devices):
    return Trainer(cfg, traffic, make_weights, devices)


def build_engine(cfg, traffic, make_weights):
    """`LLMEngine` with the constructor arguments of the traffic file's
    `engine` group; everything else is the engine's default."""
    from paddle_tpu.serving import LLMEngine
    model = _new_model(cfg)
    _set_weights(model, make_weights)
    return LLMEngine(model, **traffic["engine"])


def engine_facts(engine):
    """Shapes a reader needs to find the engine's arrays in a trace."""
    pool = engine.cache.k_pools
    shape = tuple(getattr(pool, "shape", ()))
    return {"pool_shape": list(shape), "slots": engine.max_batch_size,
            "pool_blocks": engine.cache.allocator.capacity}


def pool_blocks_held(engine):
    """Blocks of the KV pool that requests hold right now."""
    allocator = engine.cache.allocator
    return allocator.capacity - allocator.num_free


def decode_seconds(engine):
    """Host-clock seconds inside the compiled decode step since the last
    `reset_stats()` (the sum behind `stats()`'s step percentiles)."""
    return engine._stats.step_hist.sum
