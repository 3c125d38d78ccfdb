"""Sharding vocabulary + plan concretization (no devices needed)."""
import jax.numpy as jnp
import pytest

from repro.distributed.sharding import (filter_spec, pad_to_multiple,
                                        padded_heads, padded_vocab)


def test_filter_spec_drops_missing_axes():
    assert filter_spec((("pod", "data"), None, "model"),
                       ("data", "model")) == (("data",), None, "model")
    assert filter_spec(("pod",), ()) == (None,)
    assert filter_spec((None, "x"), ("x",)) == (None, "x")


def test_padding_policies():
    assert padded_heads(28, 16) == 32        # qwen2
    assert padded_heads(40, 16) == 48        # qwen3
    assert padded_heads(12, 16) == 16        # whisper
    assert padded_heads(32, 16) == 32
    assert padded_vocab(51865) == 51968      # whisper
    assert padded_vocab(152064) == 152064    # already aligned
    assert pad_to_multiple(1, 16) == 16


class _FakeMesh:
    def __init__(self, shape, names):
        import numpy as np
        self.devices = np.zeros(shape)
        self.axis_names = names
        self.axis_sizes = tuple(shape)


def test_concretize_divisibility():
    from repro.launch.plans import concretize_spec
    mesh = _FakeMesh((16, 16), ("data", "model"))
    # batch=1 cannot shard over anything
    assert concretize_spec((("pod", "data"),), (1,), mesh) == \
        __import__("jax").sharding.PartitionSpec(None)
    # 40 heads don't divide 16 -> dropped
    p = concretize_spec((None, "model"), (8, 40), mesh)
    assert tuple(p) == (None, None)
    # 128 batch over data=16 OK
    p = concretize_spec((("pod", "data"), None), (128, 4), mesh)
    assert tuple(p) == ("data", None)


def test_concretize_no_duplicate_axes():
    from repro.launch.plans import concretize_spec
    mesh = _FakeMesh((4, 4), ("data", "model"))
    p = concretize_spec(("data", ("data", "model")), (8, 8), mesh)
    flat = []
    for e in tuple(p):
        if e is None:
            continue
        flat += list(e) if isinstance(e, tuple) else [e]
    assert len(flat) == len(set(flat))


def test_train_memory_plan_shapes():
    from repro.configs.registry_configs import ALL_ARCHS
    from repro.configs.shapes import SHAPES
    from repro.launch.plans import train_memory_plan
    mesh = _FakeMesh((16, 16), ("data", "model"))
    mb, sp = train_memory_plan(ALL_ARCHS["llama-3.2-vision-90b"],
                               SHAPES["train_4k"], mesh)
    assert mb == 16
    mb2, _ = train_memory_plan(ALL_ARCHS["h2o-danube-1.8b"],
                               SHAPES["train_4k"], mesh)
    assert mb2 <= 4
    # microbatches always divide the local batch
    for arch, cfg in ALL_ARCHS.items():
        mb, _ = train_memory_plan(cfg, SHAPES["train_4k"], mesh)
        assert (SHAPES["train_4k"].global_batch // 16) % mb == 0
