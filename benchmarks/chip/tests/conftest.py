"""Fixtures of the benchmark's own tests."""

import pytest


@pytest.fixture(scope="module")
def memo_builds():
    """Build each (configuration, corpus) once per test module: a run's
    set-up otherwise rebuilds the index every time."""
    from repro.api import Deployment

    original = Deployment.from_config.__func__
    built = {}

    def from_config(cls, config, index_cache=None, dataset=None):
        key = (config.to_json(), dataset.vectors.tobytes())
        if key not in built:
            built[key] = original(cls, config, dataset=dataset)
        return built[key]

    Deployment.from_config = classmethod(from_config)
    yield built
    Deployment.from_config = classmethod(original)
