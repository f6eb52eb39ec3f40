"""Work counts and peaks: against the program's own count, hand counts for
Table 4 row 26, and the program's parameter tree for qwen3-4b."""
import dataclasses
import json
import math

import pytest

from bench import harness, work

ROW26 = dict(m=16, ps=(16,) * 6, qs=(16,) * 6)


def test_kron_forward_flops_match_the_program_count():
    from repro.core.kron import KronProblem

    for m, ps, qs in [(16, (16,) * 6, (16,) * 6), (10, (52, 65), (50, 20)),
                      (1526, (4,) * 6, (4,) * 6), (20, (512,), (512,)),
                      (7, (3, 5, 2), (4, 2, 6))]:
        assert work.kron_fwd_flops(m, ps, qs) == KronProblem(m, ps, qs).flops


def test_row26_by_hand():
    k = 16 ** 6
    fwd = 6 * 2 * 16 * k * 16  # six stages, each (16 * 16^5 slices) x (16, 16)
    assert work.kron_fwd_flops(**ROW26) == fwd == 51_539_607_552
    assert work.kron_fwdbwd_flops(**ROW26) == 3 * fwd
    factors = 6 * 16 * 16
    assert work.kron_bytes(**ROW26, itemsize=4, grad=False) == 4 * (2 * 16 * k + factors)
    assert work.kron_bytes(**ROW26, itemsize=4, grad=True) == 4 * 2 * (2 * 16 * k + factors)
    w = work.kron_call_work(**ROW26, itemsize=4, grad=True, chips=4)
    assert w["flops"] == 3 * fwd / 4


def test_rectangular_gradient_count():
    # one stage, (M, P) x (P, Q): forward, dX and dF are each 2*M*P*Q
    assert work.kron_fwdbwd_flops(5, (3,), (7,)) == 3 * 2 * 5 * 3 * 7


def test_roofline_bound_names_the_limit():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_seconds(work.kron_fwd_flops(**ROW26),
                                  work.kron_bytes(**ROW26, itemsize=4, grad=False), peak)
    assert bound == "memory" and t == pytest.approx(2.147e9 / 819e9, rel=1e-3)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v99")


def _qwen_cfg():
    return json.loads((harness.BENCH / "configs" / "qwen3-4b-kronffn.json").read_text())


@pytest.mark.parametrize("tied,total", [(True, 441_465_664), (False, 830_421_824)])
def test_qwen3_parameter_count(tied, total):
    import jax
    from repro.configs import get_config
    from repro.models import model as M

    cfg = {**_qwen_cfg(), "tie_word_embeddings": tied}
    assert work.lm_shapes(cfg)["params"] == total
    program = dataclasses.replace(get_config("qwen3-4b"), n_layers=2, kron_ffn=True,
                                  tie_embeddings=tied)
    shapes = jax.eval_shape(lambda: M.init_params(program, jax.random.PRNGKey(0)))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == total


def test_qwen3_flops_per_token_by_hand():
    cfg = _qwen_cfg()
    d, hd, h, hkv, v, s = 2560, 128, 32, 8, 151936, 512
    attn = d * h * hd * 2 + 2 * d * hkv * hd
    # kron_ffn up: (64, 40) -> (128, 76); stages run last factor first
    up = 2 * (40 * 76 * 64) + 2 * (76 * 64 * 128)  # per row, forward
    up_t = 2 * (76 * 40 * 128) + 2 * (40 * 128 * 64)  # dX chain on (128, 76) -> (64, 40)
    up_all = up + up_t + up  # dF products equal the forward's here
    down_all = up_t + up + up_t  # down's forward is up's dX chain, and back
    per_layer = 6 * attn + 2 * up_all + down_all + 12 * h * hd * s
    assert work.lm_flops_per_token(cfg, s) == 2 * per_layer + 6 * v * d
