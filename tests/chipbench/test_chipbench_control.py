"""The control of ``correct``: the plain reference computed with its
matmul operands in fp8, the nearest precision below the bf16 the
configurations state, put in the program's place, has to come out not
correct; so has the reference over one chip's quarter of the batch, which
stands for four chips that never exchange. Tiny sizes on the CPU."""

import pytest

import chipbench_tiny as tiny
from chipbench import compare
from chipbench.drivers import fit_iterator as drv


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("tinycontrol"),
                          settings={"precision": "fp32"})


@pytest.mark.parametrize("cell,seeds", [
    ("tinyyolo-fit-b256", (3, 2 ** 31 + 5)), ("resnet50-fit-b256", (4,))])
def test_the_fp8_control_is_not_correct(manifest, cell, seeds):
    """The reference in fp8 in the program's place, held to the cell's
    own limits (on the chip: six seeds a cell, PERF.md)."""
    loaded = manifest.cell(cell)
    for seed in seeds:
        batches = drv.make_batches(
            loaded["cfg"], {**loaded["traffic"], "pool": 3}, seed)
        reference = drv.reference_numbers(loaded, batches, seed)
        control = drv.reference_numbers(loaded, batches, seed, "fp8")
        ok, checks = compare.judge(compare.numbers(control, reference),
                                   loaded["limits"])
        assert not ok, checks
        same, _ = compare.judge(compare.numbers(reference, reference),
                                loaded["limits"])
        assert same


def test_a_quarter_of_the_batch_stands_for_the_exchange_left_out(manifest):
    """Four chips that never exchange each train on their own quarter: the
    reference over chip 0's rows, in the program's place, is not correct."""
    loaded = manifest.cell("resnet50-dp4-b1024")
    batches = drv.make_batches(loaded["cfg"],
                               {**loaded["traffic"], "pool": 3}, 9)
    reference = drv.reference_numbers(loaded, batches, 9)
    quarter = slice(0, int(loaded["traffic"]["batch"]) // 4)
    alone = drv.reference_numbers(loaded, batches, 9, "f32", quarter)
    ok, checks = compare.judge(compare.numbers(alone, reference),
                               loaded["limits"])
    assert not ok, checks
