"""What both builders share: the program's model filled with the seed's
weights, and where named things of the program are found."""

from __future__ import annotations

import importlib

import jax

from ..lib import weights as W

STACKED = ".blocks.block."


def resolve(spec: str):
    """``"package.module:Name"`` -> the object."""
    module, name = spec.split(":")
    return getattr(importlib.import_module(module), name)


def program_config(config: dict):
    prog = config["program"]
    return resolve(prog["config"])(**prog["config_args"])


def model_template(config: dict):
    """The program's model as shapes only, built once: a second
    construction would differ in its static fields (layer uids) and so
    in its tree structure."""
    prog = config["program"]
    return jax.eval_shape(lambda: resolve(prog["model"])(
        program_config(config), key=jax.random.PRNGKey(0)))


def seeded_model(template, key):
    """``template`` with every leaf drawn by ``lib.weights`` from ``key``
    under its pytree path, in the leaf's own dtype. Traceable, so the
    whole model is made on the device in one jitted call."""
    def fill(path, leaf):
        name = jax.tree_util.keystr(path)
        return W.leaf(key, name, leaf.shape, leaf.dtype,
                      name.startswith(STACKED))

    return jax.tree_util.tree_map_with_path(fill, template)
