"""Language-model training cells: steps of the program's compiled train
step (``repro.train.make_train_step``), one after the other on fresh rows,
dispatched ahead as a training loop dispatches them.

Configuration keys (the model's published ``config.json`` names):
``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_hidden_layers``,
``vocab_size``, ``rope_theta``, ``rms_norm_eps``, ``tie_word_embeddings``,
``qk_norm``, ``hidden_act``, and ``kron_ffn`` (``factors``), ``dtype``.
Traffic keys: ``batch``, ``seq``, ``check_steps``, ``token_pool`` (batches
of distinct rows drawn from the seed), ``optimizer`` (AdamW settings) and
``ahead_s`` (seconds of steps kept queued, ``harness.queue_depth``).

Set-up makes the weights and the token rows on the device from the seed,
builds the one compiled step and its state, and drives them through the
first ``check_steps`` steps with the window's own call, noting each step's
loss, the first gradient as the optimizer took it (its first moment over
``1 - b1``) and, after the last, each leaf's change.  The same step and
state then run the window.  After it, the float32 reference
(``reference/lm.py``) repeats those steps from the same weights and rows.
"""
from __future__ import annotations

import statistics
import time

from bench.harness import per_chip_bytes, queue_depth, seed_key

EXCLUDE_GRAD_SHARE = 1e-3  # leaves whose reference gradient is below this
                           # share of the median leaf's move by round-off alone


def make_params(shapes, key, dtype):
    """Weights for the parameter tree ``shapes`` from ``key``, by leaf name:
    embeddings N(0, 0.02^2); norm scales (offsets from 1) N(0, 0.1^2); a
    Kronecker factor set so that the product has fan-in variance; any other
    matrix N(0, 1/fan_in)."""
    import jax
    import jax.numpy as jnp

    counter = iter(range(1 << 30))

    def normal(shape, std):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def walk(node, name):
        if isinstance(node, dict):
            if "factors" in node:
                fs = node["factors"]
                d_in = 1
                for f in fs:
                    d_in *= f.shape[-2]
                std = d_in ** (-1.0 / (2 * len(fs)))
                return {**{k: walk(v, k) for k, v in node.items() if k != "factors"},
                        "factors": tuple(normal(f.shape, std) for f in fs)}
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, name) for v in node)
        if name in ("embed", "lm_head"):
            return normal(node.shape, 0.02)
        if len(node.shape) == 1 or name.endswith("norm") or name in ("ln1", "ln2"):
            return normal(node.shape, 0.1)
        return normal(node.shape, node.shape[-2] ** -0.5)

    return walk(shapes, "")


def leaf_norms(tree, scale: float = 1.0) -> dict:
    """Each leaf's Frobenius norm (times ``scale``), keyed by its path."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.linalg.norm(a.astype(jnp.float32).ravel()) * scale, t))(tree)
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(norms)}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> list[float]:
    """Each leaf's gap between the program's and the reference's norm, over
    the larger of that leaf's reference norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    if set(keys) - set(prog):
        raise KeyError(f"leaves missing from the program: {sorted(set(keys) - set(prog))}")
    median = statistics.median(ref[k] for k in keys)
    return [abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30) for k in keys]


class Driver:
    def __init__(self, cell, seed: int, devices, *, log=print):
        self.cell, self.seed, self.devices, self.log = cell, int(seed), devices, log
        t = cell.traffic
        if t["call"] != "train_step":
            raise ValueError(f"unknown LM call {t['call']!r}")
        self.batch, self.seq = int(t["batch"]), int(t["seq"])
        self.check_steps = int(t["check_steps"])
        self.pool = int(t["token_pool"])
        self.opt = dict(t["optimizer"])
        if self.opt.pop("name") != "adamw":
            raise ValueError("only AdamW is driven here")
        from repro.optim import OptConfig

        self.cfg = self.model_config()
        self.opt_cfg = OptConfig(**self.opt)

    def model_config(self):
        from repro.models.config import ModelConfig

        c = self.cell.config
        if c["hidden_act"] != "silu":
            raise ValueError(f"hidden_act {c['hidden_act']!r}: the program's SwiGLU is silu")
        return ModelConfig(
            name=self.cell.config_name, family="dense",
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            qk_norm=c["qk_norm"], rope_theta=float(c["rope_theta"]),
            norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
            kron_ffn=True, kron_factors=c["kron_ffn"]["factors"], dtype=c["dtype"],
        )

    def _inputs(self, key):
        """Weights and the pool of token rows, in one jitted call."""
        import jax
        import jax.numpy as jnp

        shapes = self._param_shapes()
        k_w, k_t = jax.random.split(key)

        def make(k_w, k_t):
            toks = jax.random.randint(k_t, (self.pool, self.batch, self.seq + 1),
                                      0, self.cfg.vocab, jnp.int32)
            return (make_params(shapes, k_w, jnp.dtype(self.cfg.dtype)),
                    [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(self.pool)])

        with jax.default_device(self.devices[0]):
            return jax.block_until_ready(jax.jit(make)(k_w, k_t))

    def _param_shapes(self):
        import jax
        from repro.models import model as M

        return jax.eval_shape(lambda: M.init_params(self.cfg, jax.random.PRNGKey(0)))

    def _program(self, state, feed):
        """The program's train step, compiled for ``state`` and ``feed``
        (arrays, or shapes with their shardings)."""
        import jax
        from repro.runtime.sharding import make_mesh
        from repro.train import make_train_step

        mesh = make_mesh((1, 1), ("data", "model"), devices=self.devices[:1])
        with jax.set_mesh(mesh):
            step = jax.jit(make_train_step(self.cfg, self.opt_cfg), donate_argnums=(0,))
            return step.lower(state, feed).compile()

    def _init_state(self, params):
        import jax
        import jax.numpy as jnp
        from repro.optim.shampoo import opt_for
        from repro.train import TrainState

        init_opt, _ = opt_for(self.opt_cfg)
        return TrainState(params, init_opt(params, self.opt_cfg), jnp.zeros((), jnp.int32))

    def compile_abstract(self):
        """The timed step compiled from shapes alone (no device needed)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(self.devices[0])
        place = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
        shapes = jax.eval_shape(
            lambda: self._init_state(make_params(self._param_shapes(), jax.random.PRNGKey(0),
                                                 jnp.dtype(self.cfg.dtype))))
        tok = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32, sharding=one)
        return self._program(place(shapes), {"tokens": tok, "labels": tok})

    def setup(self, seconds: float):
        import jax

        params, self.batches = self._inputs(seed_key(self.seed))
        self.state = jax.jit(self._init_state)(params)
        del params
        self.fn = self._program(self.state, self._feed(0))
        self.step_i = 0
        self.losses = []
        start = jax.jit(lambda t: jax.tree.map(lambda a: a.copy(), t))(self.state.params)
        times = []
        for i in range(self.check_steps):
            t = time.perf_counter()
            metrics = jax.block_until_ready((self.call(i), self.state))[0]
            times.append(time.perf_counter() - t)
            if i == 0:
                self.grad_norms = leaf_norms(self.state.opt["m"], 1.0 / (1.0 - self.opt_cfg.b1))
        self.change_norms = leaf_norms(jax.tree.map(
            lambda a, b: a.astype("float32") - b.astype("float32"),
            self.state.params, start))
        del start
        # A queued step holds its metrics; the state is donated from step to step.
        self.ahead = queue_depth(self.devices, min(times),
                                 float(self.cell.traffic.get("ahead_s", 0)),
                                 per_chip_bytes(metrics),
                                 temp_bytes=self.fn.memory_analysis().temp_size_in_bytes)

    def _feed(self, i: int) -> dict:
        tokens, labels = self.batches[i % self.pool]
        return {"tokens": tokens, "labels": labels}

    # -- the timed call -----------------------------------------------------

    def call(self, i: int):
        """Dispatch the next step; its metrics, for the window to wait on."""
        self.state, metrics = self.fn(self.state, self._feed(self.step_i))
        if self.step_i < self.check_steps:
            self.losses.append(float(metrics["loss"]))
        self.step_i += 1
        return metrics

    def kernel_names(self):
        from repro.kernels.emit import KERNEL_NAMES

        return KERNEL_NAMES

    def work(self) -> dict:
        from bench.work import lm_flops_per_token

        tokens = self.batch * self.seq
        return {"flops": lm_flops_per_token(self.cell.config, self.seq) * tokens,
                "tokens": tokens}

    # -- the check ----------------------------------------------------------

    def _reference(self, mode: str, rows: int | None = None) -> dict:
        """The reference's steps from the seed's weights and rows (the first
        ``rows`` of each batch, when given)."""
        import jax.numpy as jnp

        from bench.reference import lm as ref

        params, batches = self._inputs(seed_key(self.seed))
        feeds = [(t[:rows], l[:rows]) for t, l in batches[: self.check_steps]]
        del batches
        return ref.train_steps(self.cell.config, self.opt, params, feeds, mode=mode,
                               dtype=jnp.dtype(self.cfg.dtype))

    def _judge(self, got: dict, want: dict) -> dict:
        median = statistics.median(want["grad_norm"].values())
        moved = {k for k, g in want["grad_norm"].items()
                 if g >= EXCLUDE_GRAD_SHARE * median}
        loss_gaps = [abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"])]
        grad_gaps = leaf_gaps(got["grad_norm"], want["grad_norm"])
        values = {
            "loss_gap": max(loss_gaps),
            "grad_norm_gap": max(grad_gaps),
            "median_grad_norm_gap": statistics.median(grad_gaps),
            "change_norm_gap": max(leaf_gaps(got["change_norm"], want["change_norm"], moved)),
        }
        self.log(f"[lm] losses {got['loss']} reference {want['loss']}")
        return {k: {"value": v, "limit": float(self.cell.limits[k])}
                for k, v in values.items()}

    def check(self) -> dict:
        """The first steps' readings against the reference; the program's
        state is freed first."""
        self.state = self.fn = self.batches = None
        got = {"loss": self.losses, "grad_norm": self.grad_norms,
               "change_norm": self.change_norms}
        return self._judge(got, self._reference("highest"))

    def control(self, mode: str = "fp8", rows: int | None = None) -> dict:
        """The readings of the reference at ``mode`` (on the first ``rows``
        of each batch, when given) in the program's place."""
        return self._judge(self._reference(mode, rows), self._reference("highest"))
