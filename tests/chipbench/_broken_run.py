"""A rehearsal of one cell with the timed path broken underneath: the
rest of the run is the harness's own (``chipbench/run.py`` ``main`` with
``--rehearse``, which skips the look for a chip).

    python3 tests/chipbench/_broken_run.py <fault> <run.py arguments>

Faults a serving cell can have:

- ``token_altered``: every token is altered where it is produced -- the
  engine is handed an output head whose columns are rolled by one, so
  each of its programs emits the neighbour of the token it should; the
  reference and the step comparison keep the true head.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def token_altered():
    import jax.numpy as jnp

    from elephas_tpu import serving_engine

    sound = serving_engine.DecodeEngine.__init__

    def broken(self, params, *args, **kwargs):
        params = dict(params, head=jnp.roll(params["head"], 1, axis=-1))
        sound(self, params, *args, **kwargs)

    serving_engine.DecodeEngine.__init__ = broken


FAULTS = {"token_altered": token_altered}

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    from chipbench import run as harness

    FAULTS[sys.argv[1]]()
    sys.exit(harness.main(sys.argv[2:] + ["--rehearse"]))
