"""The trace reduction, on traces written by hand and one recorded here."""

import pytest

from bench import trace


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 9), (0, 3), (2, 4), (7, 12), (20, 20)]) == [
        (0, 4), (5, 12)]


def test_reduce_busy_idle_kernels_and_boundaries():
    ops = [
        ("fusion.1", 0, 10, None),          # chunk 1
        ("%bright_glm.3 = custom-call(s32[2] %copy.1)", 10, 25, None),
        # a custom call known by its kernel_name attribute
        ("%custom-call.7 = custom-call(f32[8] %p.2)", 25, 30,
         '%custom-call.7 = custom-call(f32[8] %p.2), '
         'custom_call_target="tpu_custom_call", kernel_name="z_candidates"'),
        # a consumer of the kernel's output is not the kernel
        ("%fold.2 = f32[8] fusion(f32[8] %bright_glm.3)", 34, 36,
         "%fold.2 = f32[8] fusion(f32[8] %bright_glm.3), calls=%z_candidates"),
        ("fusion.1", 45, 60, None),          # chunk 2, past the window's end
    ]
    modules = [("jit_chunk(1)", 0, 30), ("jit_fold", 34, 36),
               ("jit_chunk(2)", 45, 60)]
    spans = [("bench.window", -100, 100), ("bench.on_chunk", 30, 44),
             ("PjitFunction(chunk)", 40, 45)]
    r = trace.reduce(ops, modules, spans, (0, 50),
                     ["bright_glm", "z_candidates"])
    assert r["window_ns"] == 50
    assert r["busy_ns"] == 30 + 2 + 5          # [0,30], [34,36], [45,50]
    # between the chunk programs: [30, 45] is 15 long, 2 of it busy
    assert r["boundary_idle_ns"] == 13
    assert r["kernel_ns"] == {"bright_glm": 15, "z_candidates": 5}
    assert r["chunk_programs"] == 2
    top = dict(r["top_ops"])
    assert top["bright_glm.3"] == 15
    assert top["fusion.1"] == 15  # 10 + the 5 inside the window
    # gaps [30,34] and [36,45]: the longer first, named by the innermost
    # host event at its middle
    assert r["top_gaps"] == [("PjitFunction(chunk)", 9.0),
                             ("bench.on_chunk", 4.0)]


def test_leaves_drop_ops_that_contain_others():
    ops = [("%while.1 = (...) while(...)", 0, 100, None),
           ("%fusion.2 = f32[4] fusion(...)", 10, 20, None),
           ("%vmap_bright_glm_.3 = custom-call(...)", 30, 60, None),
           ("%inner.4", 35, 40, None),
           ("%after.5", 120, 130, None)]
    assert [trace.short_name(n) for n, *_ in trace.leaves(ops)] == [
        "fusion.2", "inner.4", "after.5"]
    r = trace.reduce(ops, [], [], (0, 200), ["bright_glm"])
    assert r["busy_ns"] == 10 + 5 + 10
    assert r["kernel_ns"] == {"bright_glm": 30}  # a kernel counts whole
    assert r["top_ops"][0] == ("fusion.2", 10)


@pytest.mark.parametrize("name,long_name,want", [
    ("%bright_glm.21 = (f32[2,4096,1]) custom-call(s32[2,1] %copy.4)", None,
     {"bright_glm"}),
    ("%vmap_z_candidates_.5 = s32[2,160,128] custom-call(s32[2] %a)", None,
     {"z_candidates"}),
    ("%vmap_vmap_bright_glm_.9 = f32[4] custom-call(f32[4] %b)", None,
     {"bright_glm"}),
    ("%custom-call.2 = f32[4] custom-call(f32[4] %b)",
     '%custom-call.2 = f32[4] custom-call(f32[4] %b), kernel_name="bright_glm"',
     {"custom-call", "bright_glm"}),
    # consumers that name a kernel's output, or its name elsewhere
    ("%fusion.9 = f32[4] fusion(f32[4] %bright_glm.21)", None, {"fusion"}),
    ("%copy.3 = s32[8] copy(s32[8] %vmap_z_candidates_.5)",
     "kernel_name=z_candidates in a copy's text", {"copy"}),
])
def test_kernel_names_are_the_ops_own(name, long_name, want):
    assert trace.kernel_names(name, long_name) == want


def test_reduce_with_no_ops_is_all_idle():
    r = trace.reduce([], [], [], (0, 10))
    assert r["busy_ns"] == 0 and r["top_gaps"] == [("none", 10.0)]


def test_window_span_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    devices, spans = trace.load(str(tmp_path))
    lo, hi = trace.window_of(spans)
    assert hi > lo
    assert devices == {}  # no TPU plane on a CPU
    with pytest.raises(ValueError, match="no TPU plane"):
        trace.reduce_trace(str(tmp_path))
