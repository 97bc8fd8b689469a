"""Test fixtures.

Distribution semantics are tested on a virtual 8-device CPU mesh
(``jax_num_cpu_devices``), the JAX analog of the reference's pytest-spark
``local[*]`` cluster. The platform is pinned before any backend starts.

Dataset fixtures are synthetic (no network egress): a separable 10-class
"MNIST-like" problem (784 features) and a linear-ish "housing" regression
problem (13 features), matching the shapes of the reference's fixtures
(``/root/reference/tests/conftest.py``).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest

from elephas_tpu.models import (Activation, Dense, Dropout, Input, Model,
                                Sequential)

# a time limit on every test (tests/_time_limit.py), and pytester for the
# test of that limit
pytest_plugins = ["pytester", "tests._time_limit"]


@pytest.fixture
def classification_model():
    model = Sequential()
    model.add(Dense(128, input_dim=784))
    model.add(Activation("relu"))
    model.add(Dropout(0.2))
    model.add(Dense(128))
    model.add(Activation("relu"))
    model.add(Dropout(0.2))
    model.add(Dense(10))
    model.add(Activation("softmax"))
    return model


@pytest.fixture
def regression_model():
    model = Sequential()
    model.add(Dense(64, activation="relu", input_shape=(13,)))
    model.add(Dense(64, activation="relu"))
    model.add(Dense(1, activation="linear"))
    return model


@pytest.fixture
def classification_model_functional():
    input_layer = Input(shape=(784,))
    hidden = Dense(128, activation="relu")(input_layer)
    dropout = Dropout(0.2)(hidden)
    hidden2 = Dense(128, activation="relu")(dropout)
    dropout2 = Dropout(0.2)(hidden2)
    output = Dense(10, activation="softmax")(dropout2)
    return Model(inputs=input_layer, outputs=output)


def _make_classification(n, dim, classes, seed, centers_seed=123):
    # class centers are fixed across train/test splits; only the sampling
    # noise differs, so the task is learnable and generalizes
    centers = np.random.default_rng(centers_seed).normal(0.0, 2.0,
                                                         size=(classes, dim))
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=n)
    x = centers[labels] + rng.normal(0.0, 1.0, size=(n, dim))
    x = (x - x.min()) / (x.max() - x.min())
    y = np.eye(classes)[labels]
    return x.astype("float32"), y.astype("float32")


@pytest.fixture(scope="session")
def mnist_data():
    x_train, y_train = _make_classification(1024, 784, 10, seed=0)
    x_test, y_test = _make_classification(256, 784, 10, seed=1)
    return x_train, y_train, x_test, y_test


@pytest.fixture(scope="session")
def housing_data():
    rng = np.random.default_rng(2)
    w = rng.normal(0.0, 1.0, size=13)
    x_train = rng.normal(0.0, 1.0, size=(404, 13))
    x_test = rng.normal(0.0, 1.0, size=(102, 13))
    noise = rng.normal(0.0, 0.5, size=404)
    y_train = x_train @ w + 20.0 + noise
    y_test = x_test @ w + 20.0
    return (x_train.astype("float32"), y_train.astype("float32"),
            x_test.astype("float32"), y_test.astype("float32"))
