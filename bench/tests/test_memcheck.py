"""``memcheck`` sizes any configuration: the step's arguments come from
the program, the weights' shapes from the configuration's reference
module.  The compile is for a described v5e (no chip), in a module
fixture, and every test that needs it skips when it cannot be described."""
import dataclasses
import os

import jax
import pytest

from bench import memcheck
from bench.reference import ardit as ref
from test_harness import reduced_conf


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    compilation_cache.reset_cache()
    mp.undo()


def test_check_sizes_a_configuration_dict(topo):
    out = memcheck.check(reduced_conf(), 2, 2)
    assert out["fits"] is True, out
    assert out["config"] == "ardit-self-forcing"
    # at these widths the compiler keeps no temporaries in HBM
    assert out["argument_gib"] > 0 and out["output_gib"] > 0
    assert out["temp_gib"] >= 0


def test_step_specs_lower_against_the_step():
    """The program's step takes the reference's weights and the specs'
    arguments (CPU lowering, no chip)."""
    from repro.configs.base import get_config
    from repro.models import ardit as A
    conf = reduced_conf()
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["model"])
    d = ref.dims_of(conf)
    params = jax.eval_shape(lambda: ref.make_params(
        d, 0, conf["model"]["param_dtype"]))
    step, args = memcheck.step_specs(A, cfg, 2, 2)
    assert step is A.denoise_step_paged
    step.lower(cfg, params, *args)
