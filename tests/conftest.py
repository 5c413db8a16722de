"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on 1 CPU device;
the sharded tier's multi-device cases need
XLA_FLAGS=--xla_force_host_platform_device_count=4 set before JAX starts."""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def anomaly_data():
    from repro.data.unsw_like import make_unsw_like, train_test_split
    x, y = make_unsw_like(6000, seed=0, n_features=5)
    return train_test_split(x, y)


@pytest.fixture(scope="session")
def finance_data():
    from repro.data.janestreet_like import (SWITCH_FEATURES,
                                            make_janestreet_like,
                                            train_test_split)
    x, y = make_janestreet_like(6000, seed=0)
    return train_test_split(x[:, SWITCH_FEATURES], y)
