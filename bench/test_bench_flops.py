"""Operation counts and peaks the benchmark's utilisation numbers rest
on, checked against their published sources."""
import json
import os

import pytest

from bench import flops, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def _model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


# He et al. 2016, Table 1: multiply-adds ("FLOPs") of the forward pass.
# The tolerance is 10%: Table 1 counts ResNet v1, whose downsampling
# blocks stride their first 1x1 conv, while these configurations stride
# the 3x3 conv (v1.5, as the program builds it), which puts the 3x3 conv
# of each such block at the larger resolution (+0.29e9 for ResNet-50);
# the FC layer and the projection shortcuts are counted here too.
@pytest.mark.parametrize("name,table1", [("resnet50", 3.8e9),
                                         ("resnet101", 7.6e9)])
def test_forward_macs_match_he_et_al_table1(name, table1):
    macs = flops.forward_macs_per_image(_model(name))
    assert abs(macs - table1) / table1 < 0.10
    assert macs >= table1  # v1.5 only adds


@pytest.mark.parametrize("name", ["resnet50", "resnet101"])
def test_param_count_matches_configuration(name):
    m = _model(name)
    assert flops.param_count(m) == m["param_count"]


def test_train_flops_are_three_forward_passes():
    m = _model("resnet50")
    assert flops.train_flops_per_image(m) == 6 * flops.forward_macs_per_image(m)


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
