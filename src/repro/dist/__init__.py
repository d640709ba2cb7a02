"""repro.dist — distributed runtime: sharding rules, elastic checkpoints,
fault tolerance (DESIGN.md §5).

The three modules are deliberately independent layers: ``sharding`` is pure
layout policy (no I/O), ``checkpoint`` is pure persistence (no mesh
assumptions baked into files), ``fault`` is pure control flow (drives the
other two).  Everything the models/launch/serve packages need is re-exported
here.
"""
from .checkpoint import (cleanup_old, latest_step, list_steps,
                         read_manifest, restore_checkpoint, save_checkpoint)
from .fault import (Heartbeat, RestartPolicy, StragglerMonitor,
                    run_with_restarts)
from .sharding import (batch_spec, current_mesh, default_rules,
                       in_manual_axes, logical_shard, manual_axes,
                       manual_axis_info, spec_for_axes, use_mesh)

__all__ = [
    "batch_spec", "current_mesh", "default_rules", "in_manual_axes",
    "logical_shard", "manual_axes", "manual_axis_info", "spec_for_axes",
    "use_mesh",
    "cleanup_old", "latest_step", "list_steps", "read_manifest",
    "restore_checkpoint", "save_checkpoint",
    "Heartbeat", "RestartPolicy", "StragglerMonitor", "run_with_restarts",
]
