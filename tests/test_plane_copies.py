"""benchmarks/plane_copies.py's census of a compiled program's text, on
lines kept from three programs compiled for a described v5e: the
K-EXAONE flush of one plane ``bf16[8,4608,128,128]`` through
``write_to_pages`` (a copy of the plane to the scatter's layout and a
copy back), the LFM2 burst's flush through ``write_run_to_pages`` (a
loop that carries the plane and updates it in place), and the Qwen2.5
burst's loop without its layout constraint (the carried plane in the
layout of the loop's small update, behind a copy)."""

from benchmarks.plane_copies import plane_copy_census

PLANE = 8 * 4608 * 128 * 128

WITH_COPIES = """\
ENTRY %main.9 (planes_0_.1: bf16[8,4608,128,128], news_0_.1: bf16[128,32,8,128], page_table.1: s32[128,57], positions.1: s32[128,32], valid.1: pred[128,32]) -> bf16[8,4608,128,128] {
  %planes_0_.1 = bf16[8,4608,128,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.16 = pred[128,32]{1,0:T(8,128)(4,1)S(1)} copy(%valid.1)
  %copy.10 = s32[128,32]{1,0:T(8,128)S(1)} copy(%positions.1)
  %copy.9 = bf16[8,4608,128,128]{2,0,3,1:T(8,128)(2,1)} copy(%planes_0_.1)
  %bitcast.1 = bf16[589824,8,128]{2,1,0:T(8,128)(2,1)} bitcast(%copy.9)
  %fusion.1 = bf16[589824,8,128]{2,1,0:T(8,128)(2,1)} fusion(%bitcast.1, %copy-done.1, %bitcast.12), kind=kCustom, calls=%fused_computation.1
  %bitcast.5 = bf16[8,4608,128,128]{2,0,3,1:T(8,128)(2,1)} bitcast(%fusion.1)
  ROOT %copy.17 = bf16[8,4608,128,128]{3,2,1,0:T(8,128)(2,1)} copy(%bitcast.5)
"""

IN_PLACE = """\
  %dynamic-update-slice.53 = bf16[8,4096,64,128]{3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.4519, %select.2321, %constant.1, %param_1.5210, %constant.1, %constant.1)
  %fusion.1853 = bf16[8,4096,64,128]{3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.4640, %get-tuple-element.4663), kind=kLoop, calls=%fused_computation.1765
  %while.12 = (s32[], bf16[8,4096,64,128]{3,2,1,0:T(8,128)(2,1)}, s32[256]{0:T(256)}) while(%tuple.77), condition=%cond.1, body=%body.1
"""

ASYNC = """\
  %copy-start.2 = (bf16[2,1408,128,128]{3,2,0,1:T(8,128)(2,1)}, bf16[2,1408,128,128]{3,2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%v_cache_22_.1)
  %copy-done.2 = bf16[2,1408,128,128]{3,2,0,1:T(8,128)(2,1)} copy-done(%copy-start.2)
"""


def test_a_scatter_by_token_stands_between_two_copies_of_its_plane():
    census = plane_copy_census(WITH_COPIES, PLANE)
    assert census["plane_copies"] == 2
    # The small copies are no plane's; the scatter's flat form is one.
    assert census["plane_sized"] == {"bf16[8,4608,128,128] copy": 2,
                                     "bf16[589824,8,128] fusion": 1}


def test_a_loop_that_carries_the_plane_has_none():
    census = plane_copy_census(IN_PLACE, 8 * 4096 * 64 * 128)
    assert census["plane_copies"] == 0
    assert census["plane_sized"] == {
        "bf16[8,4096,64,128] dynamic-update-slice": 1,
        "bf16[8,4096,64,128] fusion": 1, "bf16[8,4096,64,128] while": 1}


def test_an_asynchronous_copy_counts_once():
    census = plane_copy_census(WITH_COPIES + ASYNC, 2 * 1408 * 128 * 128)
    assert census["plane_copies"] == 1
    assert plane_copy_census(IN_PLACE, PLANE)["plane_sized"] == {}
