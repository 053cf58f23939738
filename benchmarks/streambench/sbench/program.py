"""The system under test, built from a configuration file.

The only module of the benchmark that builds the program (``repro``'s
``StreamServe``).  The configuration's family module (``families/``) maps
the published config keys onto the program's ``ArchConfig`` and the
benchmark's weights onto the program's parameter tree; this module checks
that tree leaf by leaf against what the program's own ``init`` would build,
and maps the file's ``serve`` section onto ``ServeConfig``.
"""
from __future__ import annotations

from types import ModuleType
from typing import Any, Dict

import jax


def serve_config(cfg: Dict[str, Any]):
    from repro.api import ServeConfig

    return ServeConfig(reduced=False, **cfg["serve"])


def program_params(family: ModuleType, cfg: Dict[str, Any], arch, w: Dict[str, Any]):
    """The program's parameter tree holding the benchmark's weights ``w``
    (consumed).  Raises if a leaf differs from what ``init`` builds."""
    from repro.distributed.sharding import unzip_params
    from repro.models import build_model

    params = family.to_program(cfg, arch, w)
    want, _ = unzip_params(jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0)))
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got_s != want_s:
        raise ValueError(f"weights do not fit the program's tree:\n{got_s}\n!=\n{want_s}")
    return params


def build(family: ModuleType, cfg: Dict[str, Any], w: Dict[str, Any]):
    """A ``StreamServe`` serving the weights ``w``."""
    from repro.api import StreamServe

    arch = family.arch_config(cfg)
    return StreamServe(serve_config(cfg), params=program_params(family, cfg, arch, w),
                       arch_cfg=arch)
