"""FLOP and byte counts against values worked by hand from the two
published configurations (depth 8)."""

import json

from benchmarks.lib import flops
from benchmarks.lib.reference import Arch
from benchmarks.tests.util import HOME


def arch(name):
    return Arch.from_config(json.loads(
        (HOME / "configs" / f"{name}.json").read_text()))


def test_internlm2_counts():
    a = arch("internlm2-1.8b-l8-train")
    # per layer: q,o 2*2048^2, k,v 2*2048*1024, MLP 3*2048*8192
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert per_layer == 62_914_560
    assert flops.matmul_params_active(a) == 8 * per_layer + 2048 * 92544
    assert flops.matmul_params_active(a) == 692_846_592
    assert flops.train_flops_per_token(a, 4096) == (
        6 * 692_846_592 + 6 * 8 * 2048 * 4096) == 4_559_732_736
    # head: 3 matmuls of 2*E*V a token
    assert flops.linear_xent_flops(a, 1) == 6 * 2048 * 92544
    # causal flash, fwd 2 + bwd 4 matmuls of T^2*D/2*2 a head
    assert flops.flash_train_flops(a, 1, 4096) == 6 * 4096 ** 2 * 2048 * 8


def test_olmoe_counts_active_parameters():
    a = arch("olmoe-1b-7b-l8-serve")
    per_layer = 4 * 2048 * 2048 + 8 * 3 * 2048 * 1024 + 2048 * 64
    assert per_layer == 67_239_936
    assert flops.matmul_params_active(a) == 8 * per_layer + 2048 * 50304
    assert flops.matmul_params_active(a) == 640_942_080
    # one decoded token at context 1000: 2N + 4*L*E*(1000+1)
    assert flops.serve_flops(a, 1000, 1) == (
        2 * 640_942_080 + 4 * 8 * 2048 * 1001)
    # a 3-token tail after 768 cached: contexts 769, 770, 771
    assert flops.serve_flops(a, 768, 3) == (
        3 * 2 * 640_942_080 + 4 * 8 * 2048 * (769 + 770 + 771))


def test_roofline_floor_names_its_bound():
    assert flops.roofline_floor_s(197e12, 1.0, 197e12, 819e9) == (
        1.0, "compute")
    t, bound = flops.roofline_floor_s(1.0, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")
