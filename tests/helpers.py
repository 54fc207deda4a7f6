import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# ---------------------------------------------------------------------------
# hypothesis shim: property tests degrade to a fixed-seed example sweep when
# the package is absent (the seed container ships without it). Import
# ``given, settings, st`` from here, never from hypothesis directly.
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    class _Strategies:
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(
                lambda rng: int(rng.integers(min_value, max_value + 1)))

        @staticmethod
        def sampled_from(options):
            options = list(options)
            return _Strategy(
                lambda rng: options[int(rng.integers(len(options)))])

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(
                lambda rng: float(rng.uniform(min_value, max_value)))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.integers(2)))

    st = _Strategies()

    def settings(max_examples: int = 10, **_ignored):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco

    def given(**strategies):
        def deco(fn):
            # NOT functools.wraps: pytest must see a ZERO-arg signature, or
            # it would treat the property arguments as fixtures
            def wrapper():
                import numpy as np
                # @settings stacks ABOVE @given, so it tags the wrapper;
                # read the attribute at call time, not decoration time
                n_examples = getattr(wrapper, "_max_examples", 10)
                rng = np.random.default_rng(0xC0FFEE)
                for i in range(n_examples):
                    kwargs = {k: s.draw(rng) for k, s in strategies.items()}
                    try:
                        fn(**kwargs)
                    except Exception as e:
                        raise AssertionError(
                            f"example {i}: {kwargs!r} failed: {e}") from e
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco


def run_subprocess(code: str, devices: int = 8, timeout: int = 600):
    """Run python code in a subprocess with N host platform devices. The
    child is pinned to the CPU backend: it wants forced host devices, and
    on a machine with a TPU the chip may be held by the parent."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def safe_eps(pts, metric, target_quantile=0.15, margin=1e-4):
    """Pick eps away from any pairwise distance (no knife-edge ties)."""
    import numpy as np
    from repro.core.metrics_host import get_host_metric
    met = get_host_metric(metric)
    d = met.true(met.cdist(pts[:200], pts[:200]))
    vals = np.unique(d[np.triu_indices(len(d), 1)])
    if len(vals) == 0:
        return 1.0
    eps = float(np.quantile(vals, target_quantile))
    while np.any(np.abs(vals - eps) < margin):
        eps += 3 * margin
    return eps
