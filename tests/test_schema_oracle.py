"""Schema-driven numerics sweep: every table op in ops/schema.yaml is
checked against a torch (preferred) or numpy oracle, auto-generated from
the schema rows — the schema is the single source of truth for the API,
the registry, the SPMD tag, AND the test matrix (reference idiom: ops.yaml
drives both codegen and the op unit-test harness, SURVEY §4)."""

import numpy as np
import pytest
import yaml

import paddle_tpu as paddle

with open("paddle_tpu/ops/schema.yaml") as _f:
    _SCHEMA = yaml.safe_load(_f)["ops"]

# ops whose math needs a custom domain to stay real/finite
_DOMAIN = {
    "acosh": lambda r: 1.0 + np.abs(r) + 0.1,
    "log": lambda r: np.abs(r) + 0.1,
    "log2": lambda r: np.abs(r) + 0.1,
    "log10": lambda r: np.abs(r) + 0.1,
    "log1p": lambda r: np.abs(r),
    "sqrt": lambda r: np.abs(r),
    "rsqrt": lambda r: np.abs(r) + 0.1,
    "reciprocal": lambda r: np.abs(r) + 0.5,
    "lgamma": lambda r: np.abs(r) + 0.5,
    "digamma": lambda r: np.abs(r) + 0.5,
    "polygamma_base": lambda r: np.abs(r) + 0.5,
    "gammaln": lambda r: np.abs(r) + 0.5,
    "erfinv": lambda r: np.clip(r, -0.9, 0.9),
    "logit": lambda r: np.clip(np.abs(r), 0.05, 0.95),
    "acos": lambda r: np.clip(r, -0.95, 0.95),
    "asin": lambda r: np.clip(r, -0.95, 0.95),
    "atanh": lambda r: np.clip(r, -0.9, 0.9),
}

# skip set + oracle resolution live in ops.coverage so the
# OPS_COVERAGE.md "oracle-verified" count is derived from the exact same
# logic this sweep runs
from paddle_tpu.ops.coverage import ORACLE_SKIP as _SKIP
from paddle_tpu.ops.coverage import resolve_oracle as _oracle


def _rows(kind):
    return [r for r in _SCHEMA if r["kind"] == kind
            and r["op"] not in _SKIP]


_INT_OPS = {"bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
            "bitwise_left_shift", "bitwise_right_shift", "gcd", "lcm"}
_COMPLEX_OPS = {"imag", "real", "conj", "angle"}


def _inputs(name, rng, arity):
    if name in _INT_OPS:
        return [rng.integers(1, 7, (3, 5)).astype(np.int32)
                for _ in range(arity)]
    if name in _COMPLEX_OPS:
        return [(rng.standard_normal((3, 5))
                 + 1j * rng.standard_normal((3, 5))).astype(np.complex64)]
    if name == "ldexp":
        return [rng.standard_normal((3, 5)).astype(np.float32),
                rng.integers(-3, 3, (3, 5)).astype(np.int32)]
    r = rng.standard_normal((3, 5)).astype(np.float32)
    first = _DOMAIN.get(name, lambda a: np.abs(a) + 0.2
                        if arity > 1 else a)(r)
    rest = [np.abs(rng.standard_normal((3, 5)).astype(np.float32)) + 0.2
            for _ in range(arity - 1)]
    return [first] + rest


def _compare(name, ours, ref):
    ours = np.asarray(ours)
    ref = np.asarray(ref)
    if ours.dtype == np.bool_ or ref.dtype == np.bool_ or \
            np.issubdtype(ours.dtype, np.integer):
        np.testing.assert_array_equal(ours, np.asarray(ref, ours.dtype),
                                      err_msg=name)
    else:
        np.testing.assert_allclose(ours, np.asarray(ref, ours.dtype),
                                   rtol=2e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("row", _rows("unary"), ids=lambda r: r["op"])
def test_unary_against_oracle(row, rng):
    name = row["op"]
    oracle = _oracle(name)
    if oracle is None:
        pytest.skip(f"no torch/numpy oracle named {name}")
    (x,) = _inputs(name, rng, 1)
    ours = getattr(paddle, name)(paddle.to_tensor(x)).numpy()
    _compare(name, ours, oracle(x))


@pytest.mark.parametrize("row", _rows("binary"), ids=lambda r: r["op"])
def test_binary_against_oracle(row, rng):
    name = row["op"]
    oracle = _oracle(name)
    if oracle is None:
        pytest.skip(f"no torch/numpy oracle named {name}")
    a, b = _inputs(name, rng, 2)
    ours = getattr(paddle, name)(paddle.to_tensor(a),
                                 paddle.to_tensor(b)).numpy()
    _compare(name, ours, oracle(a, b))


@pytest.mark.parametrize("row", _rows("reduce"), ids=lambda r: r["op"])
def test_reduce_against_numpy(row, rng):
    name = row["op"]
    npname = {"prod": "prod", "amax": "amax", "amin": "amin"}.get(name, name)
    nfn = getattr(np, npname, None)
    if nfn is None:
        pytest.skip(f"no numpy reduction named {name}")
    x = rng.standard_normal((3, 4, 5)).astype(np.float32)
    ours = getattr(paddle, name)(paddle.to_tensor(x), axis=1).numpy()
    ref = nfn(x, axis=1)
    np.testing.assert_allclose(ours, np.asarray(ref, ours.dtype),
                               rtol=2e-4, atol=1e-5, err_msg=name)


def test_oracle_coverage_is_meaningful():
    """The sweep must actually cover most of the schema, not skip it."""
    rows = _rows("unary") + _rows("binary")
    with_oracle = sum(1 for r in rows if _oracle(r["op"]) is not None)
    assert with_oracle / len(rows) >= 0.7, \
        f"only {with_oracle}/{len(rows)} schema ops have an oracle"
