"""What serving and training cells share: the program's configuration for
a cell, checked against the configuration file."""
from __future__ import annotations

import dataclasses

from .. import harness as H
from .. import weights as W


def model_config(cell: H.Cell, rehearse: bool):
    """The program's ModelConfig for the cell: the named arch with the
    file's ``overrides``; when rehearsing on the CPU, the program's smoke
    widths with the cell's ``rehearsal.model`` changes."""
    import repro.configs as cfgs
    arch = cell.config["arch"]
    if rehearse:
        return dataclasses.replace(
            cfgs.smoke_config(arch),
            **cell.spec.get("rehearsal", {}).get("model", {}))
    return dataclasses.replace(cfgs.get_config(arch),
                               **cell.config.get("overrides", {}))


def dims_for(cell: H.Cell, cfg, rehearse: bool) -> W.Dims:
    eps = float(cell.config.get("rms_norm_eps",
                                cell.config.get("layernorm_epsilon", 1e-6)))
    ran = W.Dims.of(cfg, eps)
    if not rehearse:
        stated = W.Dims.from_file(cell.config)
        if stated != ran:
            raise H.BenchError(f"{cell.config['arch']}: the configuration "
                               f"file says {stated}, the program runs {ran}")
    return ran
