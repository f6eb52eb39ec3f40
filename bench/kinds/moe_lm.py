"""Mixture-of-experts language-model training cells (DeepSeek-V2's
architecture: latent attention, a dense first layer, routed and shared
experts): steps of the program's compiled train step, driven as
``kinds/lm.py`` drives a dense model.

Configuration keys (the model's published ``config.json`` names):
``hidden_size``, ``intermediate_size`` (the dense layers),
``moe_intermediate_size``, ``n_routed_experts``, ``num_experts_per_tok``,
``n_shared_experts``, ``first_k_dense_replace``, ``norm_topk_prob``,
``routed_scaling_factor``, ``seq_aux``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rope_scaling`` (YaRN), ``num_hidden_layers``,
``vocab_size``, ``rope_theta``, ``rms_norm_eps``, ``tie_word_embeddings``;
and ``aux_loss_alpha``, ``capacity_factor``, ``kron_ffn`` (``factors``),
``dtype``.  Traffic keys as ``kinds/lm.py``.

The routed experts' projections run as one per-sample ``KronOp`` per
projection and layer, the experts its batch.  ``work()`` counts the step's
active FLOPs and the routed experts' Kron work on the ``S k`` real slots
(not the padded buckets), and hands the per-layer readers the program's
instruction -> scope map (``bench/scopes.py``).  The reference is
``reference/moe_lm.py``.
"""
from __future__ import annotations

from bench.kinds import lm
from bench.work import balanced_factors, kron_bytes, kron_fwdbwd_flops

# The configuration values this program runs; any other is refused.
REQUIRED = {"hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "q_lora_rank": None,
            "attention_bias": False, "tie_word_embeddings": False}


def expert_kron_shapes(cfg: dict) -> list[tuple[tuple, tuple]]:
    """(ps, qs) of an expert's three projections: up, gate, down."""
    d, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["kron_ffn"]["factors"]
    up = (balanced_factors(d, n), balanced_factors(f, n))
    return [up, up, (up[1], up[0])]


def ffn_kron_flops(d: int, f: int, n: int) -> int:
    """FLOPs per token of one ``kron_ffn`` SwiGLU of width ``f``, forward
    and backward."""
    up, down = balanced_factors(d, n), balanced_factors(f, n)
    return 2 * kron_fwdbwd_flops(1, up, down) + kron_fwdbwd_flops(1, down, up)


def flops_per_token(cfg: dict, seq: int) -> float:
    """Active model FLOPs per token of one training step (forward and
    backward, no recompute): 6 per matmul parameter (attention projections,
    the router, the head), the attention scores (``6 S H (dqk + dv)``, as
    PaLM's ``12 S H hd`` where the widths differ), and each token's Kron
    projections at their algorithmic count: the dense layers' FFN, the
    shared experts', and ``k`` routed experts'."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    n, layers = cfg["kron_ffn"]["factors"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    f = cfg["moe_intermediate_size"]
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    scores = 6 * seq * h * (dn + dr + dv)
    routed = sum(kron_fwdbwd_flops(1, ps, qs) for ps, qs in expert_kron_shapes(cfg))
    moe = (6 * d * cfg["n_routed_experts"] + cfg["num_experts_per_tok"] * routed
           + ffn_kron_flops(d, cfg["n_shared_experts"] * f, n))
    return (layers * (6 * attn + scores) + dense * ffn_kron_flops(d, cfg["intermediate_size"], n)
            + (layers - dense) * moe + 6 * d * cfg["vocab_size"])


def expert_kron_work(cfg: dict, tokens: int, itemsize: int) -> list[dict]:
    """Per projection of the routed experts in one step, summed over the
    expert layers: the FLOPs and least bytes of its Kron-Matmul, forward
    and backward, on the ``tokens * k`` slots routed (each expert reads and
    writes its own factors and their gradients)."""
    slots = tokens * cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    e = cfg["n_routed_experts"]
    out = []
    for ps, qs in expert_kron_shapes(cfg):
        factors = 2 * sum(p * q for p, q in zip(ps, qs)) * itemsize  # read, and dF written
        out.append({"flops": layers * kron_fwdbwd_flops(slots, ps, qs),
                    "bytes": layers * (kron_bytes(slots, ps, qs, itemsize, grad=True)
                                       + (e - 1) * factors)})
    return out


class Driver(lm.Driver):
    def model_config(self):
        from repro.models.config import ModelConfig, MoEConfig, YarnConfig

        c = self.cell.config
        for key, want in REQUIRED.items():
            if c.get(key) != want:
                raise ValueError(f"{key} = {c.get(key)!r}: this program runs only {want!r}")
        y = c["rope_scaling"]
        if y.get("type") != "yarn" or y["mscale"] != y["mscale_all_dim"]:
            raise ValueError(f"rope_scaling {y}: only yarn with mscale == mscale_all_dim "
                             "(cos/sin unscaled) is run")
        return ModelConfig(
            name=self.cell.config_name, family="moe",
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
            rope_theta=float(c["rope_theta"]),
            rope_scaling=YarnConfig(
                factor=float(y["factor"]),
                original_max_position_embeddings=y["original_max_position_embeddings"],
                beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
                mscale_all_dim=float(y["mscale_all_dim"])),
            moe=MoEConfig(
                n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
                d_expert=c["moe_intermediate_size"], n_shared=c["n_shared_experts"],
                capacity_factor=float(c["capacity_factor"]),
                norm_topk_prob=c["norm_topk_prob"],
                routed_scaling_factor=float(c["routed_scaling_factor"]),
                seq_aux=c["seq_aux"], aux_weight=float(c["aux_loss_alpha"])),
            moe_skip_first=c["first_k_dense_replace"],
            norm_eps=c["rms_norm_eps"], tie_embeddings=False,
            kron_ffn=True, kron_factors=c["kron_ffn"]["factors"], dtype=c["dtype"],
        )

    def setup(self, seconds: float):
        """As ``lm.Driver.setup``, but the weights' values before the first
        steps are kept on the host, not copied on the chip beside the
        step's temporaries, and the queue is sized from the device's peak
        alone."""
        import time

        import jax

        params, self.batches = self._inputs(lm.seed_key(self.seed))
        self.state = jax.jit(self._init_state)(params)
        del params
        self.fn = self._program(self.state, self._feed(0))
        self.step_i = 0
        self.losses = []
        start = jax.device_get(self.state.params)
        times = []
        for i in range(self.check_steps):
            t = time.perf_counter()
            metrics = jax.block_until_ready((self.call(i), self.state))[0]
            times.append(time.perf_counter() - t)
            if i == 0:
                self.grad_norms = lm.leaf_norms(self.state.opt["m"],
                                                1.0 / (1.0 - self.opt_cfg.b1))
        self.change_norms = lm.leaf_norms(jax.tree.map(
            lambda a, b: a.astype("float32") - jax.numpy.asarray(b).astype("float32"),
            self.state.params, start))
        del start
        # A queued step holds its metrics; the state is donated from step to
        # step.  The warm-up steps ran this program, so the device's peak
        # already holds its temporaries (on a v5e 11.38 GB peak beside the
        # compiler's 6.53 GB of them, more than the chip's 16.9 GB together):
        # counting them again left no room, and no step queued.
        self.ahead = lm.queue_depth(self.devices, min(times),
                                    float(self.cell.traffic.get("ahead_s", 0)),
                                    lm.per_chip_bytes(metrics))

    def work(self) -> dict:
        import jax.numpy as jnp

        from bench.scopes import op_scopes

        tokens = self.batch * self.seq
        c = self.cell.config
        return {"flops": flops_per_token(c, self.seq) * tokens, "tokens": tokens,
                "expert_kron": expert_kron_work(c, tokens, jnp.dtype(self.cfg.dtype).itemsize),
                "scopes": op_scopes(self.fn.as_text()) if self.fn is not None else {}}

    def _reference(self, mode: str, rows: int | None = None, seq: int | None = None,
                   **changes) -> dict:
        """The reference's steps from the seed's weights and rows (the first
        ``rows`` of each batch and ``seq`` positions of each row, when
        given), with the configuration's keys ``changes`` changed."""
        import jax.numpy as jnp

        from bench.reference import moe_lm as ref

        params, batches = self._inputs(lm.seed_key(self.seed))
        feeds = [(t[:rows, :seq], l[:rows, :seq]) for t, l in batches[: self.check_steps]]
        del batches
        return ref.train_steps({**self.cell.config, **changes}, self.opt, params, feeds,
                               mode=mode, dtype=jnp.dtype(self.cfg.dtype))
