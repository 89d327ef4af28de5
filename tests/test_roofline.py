"""tools/roofline.py computes %-of-peak only against the published peak of
the chip that ran: a device that is not in its table is an error."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from roofline import peak_gbs  # noqa: E402


def test_peak_is_keyed_by_device_kind():
    assert peak_gbs("TPU v5 lite") == 819.0


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
def test_a_device_without_a_published_peak_is_an_error(kind):
    with pytest.raises(SystemExit, match="no published HBM peak"):
        peak_gbs(kind)
