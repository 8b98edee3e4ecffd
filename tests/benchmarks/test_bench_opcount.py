"""The yardstick's arithmetic: model FLOPs per token, the attention's
operations and bytes, the table of peaks."""
import pytest

from benchmarks.harness import peaks


@pytest.mark.parametrize("cell, mflop", [("ernie-base.s512", 579.0),
                                         ("ernie-large.s512", 1990.0)])
def test_model_flops_per_token(man, cell, mflop):
    c = man.cell(cell)
    fn = man.function("opcounts", "ernie:train_flops_per_item")
    got = fn(man.config(c["config"])["model"], man.json_of("traffic", c["traffic"]))
    assert got / 1e6 == pytest.approx(mflop, rel=5e-3)


def test_flash_attention_cost_of_cell_1(man):
    c = man.cell("ernie-base.s512")
    cost = man.function("opcounts", "ernie:flash_attention_train")(
        man.config(c["config"])["model"], man.json_of("traffic", c["traffic"]))
    assert cost["ops"] == 12 * 64 * 512 * 512 * 768 * 12
    assert cost["bytes"] == 12 * 64 * 512 * 768 * 2 * 12
    v5e = peaks.lookup("TPU v5 lite")
    # compute-bound by a little: 9.4 ms of operations, 8.9 ms of bytes
    assert cost["ops"] / v5e["bf16_flops_per_s"] > \
        cost["bytes"] / v5e["hbm_bytes_per_s"]


def test_peaks_table_is_the_public_v5e_and_refuses_the_unknown():
    v5e = peaks.lookup("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.lookup("cpu")
