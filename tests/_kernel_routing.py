"""The Pallas kernels a call launches, by name, read off its jaxpr.

Backend-independent, like ``launch_count``: interpret-mode kernels keep
their ``pallas_call`` equations and names, so a CPU test sees the routing a
TPU run dispatches.  Tests use it to assert which path ``bd_impl`` takes.
"""
import jax

from repro.launch.launch_count import _sub_jaxprs


def kernel_names(fn, *args, **kwargs) -> set:
    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.add(str(eqn.params["name"]))
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)
    return names
