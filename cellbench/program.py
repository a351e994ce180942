"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program. It builds what
the windows drive and nothing else: the jitted train step with its state
(``make_resilient_train_step`` on one chip or under ``shard_map`` on a
mesh), and the supervised serving engine. Weights come from
:mod:`cellbench.weights`, re-laid into the program's tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import weights as W


def model_for(config: dict):
    from apex_tpu.models import GPTModel, TransformerConfig

    sz = W.sizes(config)
    t = config.get("training", {})
    return GPTModel(TransformerConfig(
        num_layers=sz["L"], hidden_size=sz["h"],
        num_attention_heads=sz["heads"], vocab_size=sz["V"],
        max_position_embeddings=sz["pos"], hidden_dropout=0.0,
        attention_dropout=0.0, layernorm_epsilon=sz["eps"],
        init_method_std=sz["std"], recompute=bool(t.get("recompute", False)),
        scan_unroll=sz["L"] if t.get("scan_unroll") == "depth" else 1,
        compute_dtype=jnp.bfloat16))


def _mesh(shape: dict):
    from apex_tpu.transformer import parallel_state

    n = int(np.prod(list(shape.values())))
    if n == 1:
        return None
    parallel_state.destroy_model_parallel()
    return parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=int(shape.get("tensor", 1)),
        devices=jax.devices()[:n])


class Trainer:
    """The compiled step and its state: ONE object, driven through the
    checked first steps and then handed to the window."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu import amp
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.resilience import (make_resilient_train_step,
                                         make_train_state)

        self.sz = W.sizes(config)
        t = config["training"]
        model = model_for(config)
        amp_state = amp.initialize(t["amp_opt_level"])
        policy, scaler = amp_state.policy, amp_state.scaler
        self.opt = FusedAdam(lr=t["lr"], betas=tuple(t["betas"]),
                             eps=t["adam_eps"], weight_decay=0.0,
                             master_weights=True)
        self.mesh = _mesh(traffic.get("mesh", {}))

        def loss_fn(p, b, rng):
            return model.apply(p, b["tokens"], b["labels"])

        def initial_params(key):
            return policy.cast_to_param(W.program_tree(
                W.canonical(key, self.sz), self.sz))

        def make_state(key):
            params = initial_params(key)
            return make_train_state(params, self.opt.init(params),
                                    scaler.init())

        key = W.key_from_seed(seed)
        if self.mesh is None:
            self.step = make_resilient_train_step(loss_fn, self.opt, scaler)
            self._batch_sharding = None
            self.state = jax.jit(make_state)(key)
        else:
            spec = model.spec()
            template = jax.eval_shape(initial_params, key)
            bspec = {"tokens": P("data"), "labels": P("data")}
            self.step = make_resilient_train_step(
                loss_fn, self.opt, scaler, mesh=self.mesh, param_spec=spec,
                batch_spec=bspec, params_template=template)
            state_spec = {
                "params": spec,
                "opt_state": self.opt.state_spec(template, spec),
                "step": P(),
                "scaler": jax.tree.map(lambda _: P(), scaler.init())}
            named = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), state_spec,
                is_leaf=lambda s: isinstance(s, P))
            self._batch_sharding = NamedSharding(self.mesh, P("data"))
            self.state = jax.jit(make_state, out_shardings=named)(key)

        b1 = self.opt.betas[0]

        def first_grad_norms(state):
            # Adam's first moment after one step is (1 - b1) * g: the
            # gradient as the optimizer got it, unscaled and synced
            g = jax.tree.map(lambda m: m / (1.0 - b1),
                             state["opt_state"]["slots"]["exp_avg"])
            return _norms(W.canonical_names(g), self.sz["heads"])

        def update_norms(state, key):
            p0 = jax.tree.map(lambda x: x.astype(jnp.float32),
                              initial_params(key))
            d = jax.tree.map(jnp.subtract, state["opt_state"]["master"], p0)
            return _norms(W.canonical_names(d), self.sz["heads"])

        self.first_grad_norms = jax.jit(first_grad_norms)
        self.update_norms = jax.jit(update_norms)
        self._key = key

    def feed(self, rows: np.ndarray) -> dict:
        """Host rows ``[batch, seq + 1]`` -> the step's batch on the device."""
        batch = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
        if self._batch_sharding is None:
            return jax.device_put(batch)
        return jax.device_put(batch, self._batch_sharding)

    def advance(self, batch: dict) -> dict:
        self.state, metrics = self.step(self.state, batch, None)
        return metrics

    def read_update_norms(self):
        return self.update_norms(self.state, self._key)

    def release(self) -> None:
        self.state = None


def _norms(named: dict, heads: int) -> dict:
    """L2 norm per leaf, one per layer for leaves stacked on a layer axis.
    The fused QKV projection counts as three leaves (q, k, v): a key's
    bias has no gradient under softmax, and must not hide in a sum. Its
    rows are grouped per head as ``[q_h | k_h | v_h]`` in the program."""
    out = {}

    def norm(x, stacked):
        sq = jnp.square(x.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(sq.reshape(x.shape[0], -1), axis=1)
                        if stacked else jnp.sum(sq)[None])

    for k, x in named.items():
        if k in ("w_qkv", "b_qkv"):
            layers = x.shape[0]
            parts = x.reshape(layers, heads, 3, -1)
            for i, part in enumerate("qkv"):
                out[f"{k[0]}_{part}"] = norm(parts[:, :, i], True)
        else:
            out[k] = norm(x, k not in ("wte", "wpe", "lnf_g", "lnf_b"))
    return out


class Server:
    """The supervised engine, as ``python -m apex_tpu.loadtest`` runs it:
    ``EngineSupervisor.submit`` and ``.tick`` are the two calls the window
    makes."""

    def __init__(self, config: dict, seed: int):
        from apex_tpu.observability import MetricsRegistry
        from apex_tpu.serving import EngineConfig, EngineSupervisor

        self.sz = W.sizes(config)
        s = config["serving"]
        model = model_for(config)
        params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16),
            W.program_tree(W.canonical(key, self.sz), self.sz)))(
                W.key_from_seed(seed))
        self.engine_config = EngineConfig(
            max_slots=s["max_slots"], max_len=s["max_len"],
            page_size=s["page_size"])
        self.registry = MetricsRegistry(histogram_bound=1 << 16)
        self.supervisor = EngineSupervisor(
            model, params, self.engine_config, metrics=self.registry)
        del params
        self.n_pages = s["max_slots"] * self.engine_config.pages_per_slot

    def request(self, plan):
        from apex_tpu.serving import Request, SamplingParams

        sampling = (SamplingParams() if plan.greedy else SamplingParams(
            temperature=0.7, top_k=40, seed=plan.sample_seed))
        return Request(prompt=plan.prompt,
                       max_new_tokens=plan.max_new_tokens, sampling=sampling)

    def submit(self, request) -> bool:
        """True when the program took the request; a shed or refused
        request is the program's answer, not an error of the harness."""
        from apex_tpu.serving.scheduler import (DeadlineExpiredError,
                                                QueueFullError)
        from apex_tpu.serving.supervisor import EngineUnavailableError

        try:
            self.supervisor.submit(request)
            return True
        except (QueueFullError, DeadlineExpiredError,
                EngineUnavailableError):
            return False

    def tick(self) -> list:
        return self.supervisor.tick()

    def inflight(self) -> list:
        return self.supervisor.engine.inflight()

    def queued(self) -> int:
        return self.supervisor.queued_count

    def active(self) -> int:
        return self.supervisor.active_count

    def close(self) -> None:
        self.supervisor.close()
        self.supervisor = None
