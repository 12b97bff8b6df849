"""Whole-stage fusion compiler (ROADMAP item 2).

The converted physical plan dispatches one jitted kernel per operator per
batch, and for chains of tiny kernels the python dispatch gap between
them — not device time — can dominate (PR 6's device/transfer/dispatch
breakdown names it per operator).
This subsystem collapses each fusible pipeline into ONE compiled
program:

  * ``cutter``    — walks the converted plan and cuts maximal chains of
    fusible operators at exchange/scan/fallback boundaries (the same
    boundaries AQE's stage cutting keys on — a hash exchange is a stage
    edge in both worlds; see sql/adaptive/executor._is_stage_boundary
    for the CPU-plan twin this reuses the shape of);
  * ``fusedexec`` — ``TpuFusedStageExec``, the first-class plan node
    that runs the whole member pipeline as one ``cached_jit`` program
    and reports member-operator identity to the compile ledger, the
    profile tree, progress records and the flight recorder.

Gate: ``spark.rapids.sql.fusion.stageEnabled`` (default false — today's
per-operator plans stay byte-identical; bench turns it on).
"""

from spark_rapids_tpu.exec.stagecompiler.cutter import compile_stages
from spark_rapids_tpu.exec.stagecompiler.fusedexec import TpuFusedStageExec

__all__ = ["compile_stages", "TpuFusedStageExec"]
