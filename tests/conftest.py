import warnings

import numpy as np
import pytest

warnings.filterwarnings("ignore", message=".*int64.*")


@pytest.fixture(scope="session", autouse=True)
def _no_persistent_compile_cache():
    """Entry points the suite drives (the serve CLI) turn JAX's persistent
    compilation cache on; the suite itself writes none into the checkout."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


@pytest.fixture(scope="session", autouse=True)
def _isolated_tune_cache(tmp_path_factory):
    """Keep the kernel block-size tuner hermetic: the suite must neither
    read a developer's warm tuning entries nor write into them."""
    import os

    from repro.kernels import tune

    path = str(tmp_path_factory.mktemp("tune") / "tune_cache.json")
    old = os.environ.get("REPRO_TUNE_CACHE")
    os.environ["REPRO_TUNE_CACHE"] = path
    tune.clear_memory_cache()
    yield
    if old is None:
        os.environ.pop("REPRO_TUNE_CACHE", None)
    else:
        os.environ["REPRO_TUNE_CACHE"] = old
    tune.clear_memory_cache()


@pytest.fixture(scope="session")
def blobs():
    """Small, clearly separable 3-class dataset for fast pipeline tests."""
    rng = np.random.RandomState(0)
    n, f, c = 900, 12, 3
    means = rng.randn(c, f) * 4.0
    y = rng.randint(0, c, n).astype(np.int32)
    x = (means[y] + rng.randn(n, f)).astype(np.float32)
    return x[:600], y[:600], x[600:], y[600:], c
