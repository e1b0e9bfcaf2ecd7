"""Every f32 contraction of the fit, the encoders, the served steps and
the streaming refit names its matmul precision (``linalg.F32``, HIGHEST
in the kernels): none is left to the backend's default, which on a TPU
rounds f32 inputs to bf16 and costs recall. The check walks the traced
jaxprs, so it holds on the CPU, where the default is exact anyway."""
import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import gleanvec as gv, leanvec_sphering as lvs
from repro.core import search as msearch
from repro.core import streaming
from repro.data import vectors
from repro.launch import serve

pytestmark = pytest.mark.tier1

HIGHEST = jax.lax.Precision.HIGHEST


def _subjaxprs(value):
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _where(eqn) -> str:
    """The innermost ``repro`` source line that traced ``eqn``."""
    tb = eqn.source_info.traceback
    for frame in (tb.frames if tb is not None else []):
        if "/repro/" in frame.file_name:
            return f"{frame.file_name}:{frame.line_num}"
    return str(eqn)


def loose_dots(fn, *args) -> list:
    """``dot_general`` equations of ``fn(*args)``'s jaxpr, nested calls,
    loops and kernels included, whose precision is not HIGHEST."""
    loose, todo = [], [jax.make_jaxpr(fn)(*args).jaxpr]
    while todo:
        for eqn in todo.pop().eqns:
            if eqn.primitive.name == "dot_general":
                prec = eqn.params["precision"]
                if not isinstance(prec, tuple):
                    prec = (prec, prec)
                if prec != (HIGHEST, HIGHEST):
                    loose.append(_where(eqn))
            for value in eqn.params.values():
                todo.extend(_subjaxprs(value))
    return loose


@pytest.fixture(scope="module")
def data():
    ds = vectors.make_dataset("prec", n=4096, d=32, n_queries=64, ood=True,
                              seed=3)
    return ds, jnp.asarray(ds.database), jnp.asarray(ds.queries_learn)


def test_fits(data):
    _, x, q = data
    assert loose_dots(lambda q, x: gv.fit(jax.random.PRNGKey(0), q, x, c=4,
                                          d=16), q, x) == []
    assert loose_dots(lambda q, x: lvs.fit(q, x, 16), q, x) == []


SERVED = {
    "flat-gleanvec-int8": ["--mode", "gleanvec-int8"],
    "flat-sphering-int8": ["--mode", "sphering-int8"],
    "flat-gleanvec": ["--mode", "gleanvec"],
    "flat-full": ["--mode", "full"],
    "ivf-aligned": ["--mode", "gleanvec-int8-sorted", "--index", "ivf",
                    "--aligned", "--reduced-probe"],
    "ivf": ["--mode", "gleanvec-int8", "--index", "ivf"],
    "graph-fused": ["--mode", "gleanvec-int8-sorted", "--index", "graph",
                    "--fused-graph"],
    "sharded": ["--mode", "gleanvec-int8", "--shards", "2"],
}


@pytest.mark.parametrize("name", list(SERVED))
def test_encode_and_served_step(data, name):
    ds, x, q = data
    args = serve.parser().parse_args(
        ["--n", "4096", "--dim", "32", "--d", "16", "--clusters", "4",
         "--lists", "8", "--nprobe", "2", "--max-hops", "8",
         *SERVED[name]])
    model = serve.fit_model(args, ds)
    # the sorted layouts sort on the host after the same encode
    if model is not None and not args.mode.endswith("-sorted"):
        assert loose_dots(lambda x: msearch.build_artifacts(
            args.mode, x, model).scorer, x) == []
    state = serve.build_state(args, ds.database, model)
    assert loose_dots(lambda qt, s: msearch.state_search(qt, s, 10, 50),
                      jnp.asarray(ds.queries_test[:8]), state) == []


def test_streaming_refit(data):
    _, x, q = data
    model = gv.fit(jax.random.PRNGKey(0), q, x, c=4, d=16)
    art = streaming.build_streaming_artifacts("gleanvec-int8", x, model)
    state = streaming.init_from_artifacts(art, q)
    assert loose_dots(
        lambda s, rows: streaming.refresh(streaming.observe_queries(
            streaming.insert(s, rows), q)), state, x[:64]) == []
    assert loose_dots(streaming.transition_matrix,
                      streaming.refresh(state)) == []
