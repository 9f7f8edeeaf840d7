"""JX01 jit purity: traced functions must not print, mutate module state,
or write in place into traced arguments."""
from analysis import analyze_text


def jx01(path, src):
    return [f for f in analyze_text(path, src) if f.code == "JX01"]


_DECORATED = """\
import jax

STATS = {"calls": 0}

@jax.jit
def bad(x):
    print("tracing")        # trace-time only
    STATS["calls"] += 1     # module-state mutation
    x[0] = 1                # in-place write on a tracer
    return x
"""

_WRAPPED = """\
import jax

def kernel(buf, v):
    buf.fill(v)
    return buf

_jit_kernel = jax.jit(kernel)
"""

_PARTIAL = """\
import jax
from functools import partial

@partial(jax.jit, static_argnums=0)
def bad(n, arr):
    global TOTAL
    TOTAL = n
    return arr
"""

_SHARD_MAP = """\
import jax
from jax import shard_map

def step(x):
    x[:] = 0
    return x

fn = jax.jit(shard_map(step, mesh=None, in_specs=None, out_specs=None))
"""

_ALIASED_IMPORT = """\
from jax import jit as J

@J
def bad(x):
    print(x)
    return x
"""

_PURE = """\
import jax
import jax.numpy as jnp

@jax.jit
def good(x, known):
    pool = jnp.zeros((4, 8))
    pool = jax.lax.dynamic_update_slice(pool, known, (0, 0))
    y = x.at[0].set(5)          # functional update
    outs = []
    for i in range(3):
        outs.append(y)          # local list: fine
    table = {}
    table["k"] = y              # local dict: fine
    for row in outs:
        z = row[0]              # loop-bound name reads
    return pool, y, z

def untraced(x):
    print(x)                    # not traced: not JX01's business
    x[0] = 1
    return x
"""


def test_jx01_flags_decorated_function():
    assert [f.line for f in jx01("m.py", _DECORATED)] == [7, 8, 9]


def test_jx01_flags_function_passed_to_jit():
    assert [f.line for f in jx01("m.py", _WRAPPED)] == [4]


def test_jx01_flags_partial_jit_decorator():
    # reported at the global declaration inside the traced function
    assert [f.line for f in jx01("m.py", _PARTIAL)] == [6]


def test_jx01_flags_shard_map_target():
    assert [f.line for f in jx01("m.py", _SHARD_MAP)] == [5]


def test_jx01_resolves_import_aliases():
    assert [f.line for f in jx01("m.py", _ALIASED_IMPORT)] == [5]


def test_jx01_ignores_pure_and_untraced():
    assert jx01("m.py", _PURE) == []


def test_jx01_nested_helper_locals_are_not_module_state():
    # the canonical scan/body-function pattern: a nested helper mutating
    # its OWN locals is pure
    src = ("import jax\n"
           "@jax.jit\n"
           "def outer(x):\n"
           "    def init(n):\n"
           "        buf = {}\n"
           "        buf['a'] = n\n"
           "        return buf\n"
           "    return init(3), x\n")
    assert jx01("m.py", src) == []
