"""``packed_bytes_per_step`` on lines cut from ``bert_base_s512_x4``'s
compiled step as the commit before PR 25 made it (``packed_bytes_hlo.txt``:
operand lists and ``backend_config`` trimmed, nothing else): the 529 MB
flat gradient buffer as one ``concatenate``, the fused update's two
small buffers each as a fusion of a dynamic-update-slice that kept the
call's ``op_name``, a ``concatenate`` of the model's that is no packing,
and the fused computations, whose roots carry the name a second time."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.harness import spec  # noqa: E402

METRIC = spec.load_module("metrics", "packed_bytes_per_step")
with open(os.path.join(HERE, "packed_bytes_hlo.txt")) as f:
    HLO = f.read()

FLAT, SMALL = 132361530 * 4, 121344 * 4
ROOT = next(line for line in HLO.splitlines()
            if line.startswith("  ROOT %constant_dynamic-update-slice_fusion = "))
CHAINED = HLO.replace(ROOT, ROOT.replace(
    "ROOT %constant_dynamic-update-slice_fusion = ",
    "%constant_dynamic-update-slice_fusion.9 = ") + "\n" + ROOT)


def _without(text, *needles):
    return "\n".join(line for line in text.splitlines()
                     if not any(n in line for n in needles))


@pytest.mark.parametrize("text, steps_per_call, expected", [
    (HLO, 1, float(FLAT + 2 * SMALL)),
    # what is left once the large leaves go alone: the small buffers
    (_without(HLO, "%concatenate.17 = ", "%psum.14 = "), 1,
     float(2 * SMALL)),
    # one more link of a buffer's chain under the same name: one call
    (CHAINED, 1, float(FLAT + 2 * SMALL)),
    # the same call at another result shape is another buffer
    (CHAINED.replace("fusion.9 = f32[121344]", "fusion.9 = f32[768]"), 1,
     float(FLAT + 2 * SMALL + 768 * 4)),
    # a step without the program's names: nothing to count, not nothing
    (HLO.replace("hvd_pack/", ""), 1, 0.0),
    # a scan-fused step's text holds a loop body, not a step
    (HLO, 4, None),
], ids=["parent_step", "small_buffers_only", "chain_counts_once",
        "another_shape_counts", "no_names", "scan_fused"])
def test_packed_bytes_of_the_checked_in_step(text, steps_per_call, expected):
    context = types.SimpleNamespace(system=types.SimpleNamespace(
        hlo_text=text, steps_per_call=steps_per_call))
    assert METRIC.read(context) == expected


def test_the_snippet_is_what_the_test_says():
    assert CHAINED.count("\n") == HLO.count("\n") + 1
    assert HLO.count('hvd_pack/concatenate"') == 5  # 2 roots, 3 at the top
    assert "%concatenate.22 = s32[16,512,3]" in HLO
