"""Sharding-rule properties (hypothesis): specs always valid for the mesh —
axes never repeated, sharded dims always divisible — plus concrete checks of
the TP/FSDP/ZeRO layouts on the production mesh."""
import math

import jax
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.train import sharding as shd
from repro.train.steps import param_specs


@pytest.fixture(scope="module")
def mesh():
    # host has 1 device: an abstract mesh stands in for the 16x16 pod
    from jax.sharding import AbstractMesh, AxisType
    return AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def _canon(spec):
    """PartitionSpec may store ('data',) as 'data'; compare canonically."""
    out = []
    for e in spec:
        if e is None or isinstance(e, str):
            out.append(e)
        elif isinstance(e, tuple) and len(e) == 1:
            out.append(e[0])
        else:
            out.append(tuple(e))
    return tuple(out)


def _spec_axes(spec):
    axes = []
    for e in spec:
        if e is None:
            continue
        axes.extend(e if isinstance(e, tuple) else (e,))
    return axes


def _check_valid(spec, shape, mesh):
    axes = _spec_axes(spec)
    assert len(axes) == len(set(axes)), f"repeated axis in {spec}"
    for dim, e in zip(shape, tuple(spec) + (None,) * len(shape)):
        if e is None:
            continue
        es = e if isinstance(e, tuple) else (e,)
        total = math.prod(mesh.shape[a] for a in es)
        assert dim % total == 0, f"{spec} does not divide {shape}"


NAMES = ["table", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in",
         "w_out", "wq_a", "wq_b", "wkv_a", "wkv_b", "router", "scale",
         "conv_w", "a_log", "d_skip", "w_xproj", "w_dt", "u", "mix"]


@settings(max_examples=200, deadline=None)
@given(
    name=st.sampled_from(NAMES),
    grouped=st.booleans(),
    dims=st.lists(st.sampled_from([1, 3, 8, 16, 48, 64, 96, 576, 2048, 4096,
                                   16384, 49152, 92553]), min_size=1, max_size=3),
)
def test_param_pspec_always_valid(mesh, name, grouped, dims):
    cfg = get_config("qwen3-14b")
    shape = tuple(([4] if grouped else []) + dims)
    path = ("groups/l0/mixer/" if grouped else "") + name
    spec = shd.param_pspec(path, shape, mesh, cfg)
    assert len(tuple(spec)) <= len(shape)
    _check_valid(spec, shape, mesh)
    if grouped:
        assert tuple(spec)[0] is None          # stacked axis never sharded


@settings(max_examples=100, deadline=None)
@given(dims=st.lists(st.integers(1, 4096), min_size=1, max_size=4))
def test_zero1_always_valid(mesh, dims):
    spec = shd.zero1_pspec(P(), tuple(dims), mesh)
    _check_valid(spec, tuple(dims), mesh)


@settings(max_examples=100, deadline=None)
@given(
    b=st.sampled_from([1, 2, 16, 32, 128, 256]),
    hkv=st.sampled_from([1, 3, 4, 8, 16, 128]),
    t=st.sampled_from([128, 4096, 32768, 524288]),
)
def test_cache_pspec_always_valid(mesh, b, hkv, t):
    cfg = get_config("qwen3-14b")
    shape = (4, b, hkv, t, 128)
    spec = shd.cache_pspec("groups/l0/self/k", shape, mesh, cfg)
    _check_valid(spec, shape, mesh)


def test_tp_layout_on_production_mesh(mesh):
    cfg = get_config("qwen3-14b")
    specs = param_specs(cfg, jax.numpy.bfloat16)
    gp = specs["groups"]["l0"]
    wq = shd.param_pspec("groups/l0/mixer/wq", gp["mixer"]["wq"].shape, mesh, cfg)
    assert _canon(wq) == (None, "data", "model")      # column TP + FSDP on d
    wo = shd.param_pspec("groups/l0/mixer/wo", gp["mixer"]["wo"].shape, mesh, cfg)
    assert _canon(wo) == (None, "model", "data")      # row TP + FSDP on d
    # vocab 151936 divides 16 -> embedding vocab-sharded
    emb = shd.param_pspec("embed/table", specs["embed"]["table"].shape, mesh, cfg)
    assert tuple(emb)[0] == "model"


def test_fsdp_applies_for_giant_archs(mesh):
    cfg = get_config("deepseek-v3-671b")
    spec = shd.param_pspec("groups/l0/ffn/w_gate", (61, 256, 7168, 2048),
                           mesh, cfg)
    # experts over model (EP) + d_model over data (FSDP)
    assert _canon(spec) == (None, "model", "data", None)


def test_internvl_vocab_not_divisible_replicates(mesh):
    cfg = get_config("internvl2-26b")
    spec = shd.param_pspec("embed/table", (92553, 6144), mesh, cfg)
    assert tuple(spec)[0] is None                 # 92553 % 16 != 0


def test_long_context_cache_seq_sharded(mesh):
    cfg = get_config("jamba-1.5-large-398b")
    # batch=1 -> B unshardable; kv=8 < 16 -> heads unshardable; seq picks up
    # (data x model) = 256-way sharding
    spec = shd.cache_pspec("groups/l0/self/k", (9, 1, 8, 524288, 128), mesh, cfg)
    assert tuple(spec)[3] == ("data", "model")
