"""RegNetY-4.0GF: the paper's generating rule and the graph it builds."""
import pytest

from repro.models.regnet import RegNetConfig, regnet_stages
from repro.models.registry import get_cnn_api


def test_rule_gives_the_published_stages():
    """Radosavovic et al.'s quantized linear rule at RegNetY-4.0GF's
    parameters: 128 / 192 / 512 / 1088 channels, 2 / 6 / 12 / 2 blocks,
    groups of 64 channels."""
    assert RegNetConfig().stages() == [
        (128, 2, 2), (192, 6, 3), (512, 12, 8), (1088, 2, 17)]


@pytest.mark.parametrize(
    "params,widths,depths",
    [
        # pycls RegNetY-8.0GF and RegNetY-32GF
        ((17, 192, 76.82, 2.19, 56), [168, 448, 896, 2016], [2, 4, 10, 1]),
        ((20, 232, 115.89, 2.53, 232), [232, 696, 1392, 3712], [2, 5, 12, 1]),
    ],
    ids=["8.0GF", "32GF"],
)
def test_rule_gives_other_published_regnets(params, widths, depths):
    stages = regnet_stages(*params)
    assert [w for w, _, _ in stages] == widths
    assert [n for _, n, _ in stages] == depths


def test_every_block_has_a_grouped_conv_and_a_gate():
    api = get_cnn_api("regnety_4gf")
    g = api.graph(api.make_config())
    grouped = [n for n in g.topo_order() if g.spec(n).groups > 1]
    assert len(grouped) == 22
    assert {g.spec(n).groups for n in grouped} == {2, 3, 8, 17}
    assert all(g.spec(n).group_in == 64 and g.spec(n).kernel == (3, 3)
               for n in grouped)
    assert sum(n.endswith("_scale") for n in g.topo_order()) == 22
    # stride on the grouped conv; a strided 1x1 projection where the shape changes
    assert g.spec("s1b1_b").stride == (2, 2) and g.spec("s1b1_a").stride == (1, 1)
    assert g.spec("s1b1_proj").stride == (2, 2) and "s1b2_proj" not in g
    assert g.preds("s1b1_add") == ["s1b1_c", "s1b1_proj"]
    assert g.preds("s1b2_add") == ["s1b2_c", "s1b1_add"]
    assert g.spec("s1b2_add").activation == "relu"
    # the gate reduces to round(0.25 x the block's input width), with ReLU
    assert g.spec("s1b1_se_reduce").d_out == 8  # of the stem's 32
    assert g.spec("s2b1_se_reduce").d_out == 32  # of 128, not of 192
    assert g.spec("s2b1_se_reduce").activation == "relu"
    assert g.spec("s2b1_se_expand").activation == "sigmoid"
    assert g.preds("s2b1_scale") == ["s2b1_b", "s2b1_se_expand"]
    assert g.spec("s4b2_c").activation == "none"
    assert g.spec("fc").d_in == 1088
