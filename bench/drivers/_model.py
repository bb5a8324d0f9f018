"""What serving and training cells share: the program's configuration for
a cell, checked against the configuration file."""
from __future__ import annotations

import dataclasses

from .. import harness as H


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


def dims_for(cell: H.Cell, cfg, rehearse: bool):
    """The sizes of what the program runs, by the cell's family module; at
    full size they have to be those the configuration file states."""
    family = H.family(cell)
    ran = family.program_dims(cfg, cell.config)
    if not rehearse:
        stated = family.file_dims(cell.config)
        if stated != ran:
            raise H.BenchError(f"{cell.config['arch']}: the configuration "
                               f"file says {stated}, the program runs {ran}")
    return ran
