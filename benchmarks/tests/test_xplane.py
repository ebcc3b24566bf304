"""The raw ``.xplane.pb`` reader against jax's own, on a CPU trace."""
import glob

import pytest


def test_reader_agrees_with_profile_data(tmp_path):
    import jax
    import jax.numpy as jnp

    from harness import trace, xplane

    @jax.jit
    def f(x):
        with jax.named_scope("lgbm/split_scan"):
            return (jnp.sin(x) @ x).sum()
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with trace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench/traced_window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench/update"):
                    f(x).block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    mine = {p["name"]: p for p in xplane.read(path)}
    theirs = jax.profiler.ProfileData.from_file(path)
    seen = 0
    for plane in theirs.planes:
        lines = {ln["name"]: ln for ln in mine[plane.name]["lines"]}
        for line in plane.lines:
            got = lines[line.name]["events"]
            want = list(line.events)
            assert len(got) == len(want)
            for (s, d, n, _), ev in zip(got, want):
                assert n == ev.name
                assert s == pytest.approx(ev.start_ns, abs=1.0)
                assert d == pytest.approx(ev.duration_ns, abs=1.0)
                seen += 1
    assert seen > 10
    parsed = trace.parse_xplane(path)
    assert [h[2] for h in parsed["host"]].count("bench/update") == 2
    t0, t1 = trace.window_of(parsed, "bench/traced_window")
    assert t1 > t0 and trace.busy_seconds(trace.clip(parsed, t0, t1)) > 0
