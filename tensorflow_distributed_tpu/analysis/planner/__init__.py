"""Auto-layout planner: model + chip count in, launch config out.

The layout-assignment problem the reference scripts solved by EDITING
THREE SCRIPT COPIES (ps/worker roles and task indices were literally
the only diff), Mesh-TensorFlow posed as a per-model search, and the
pjit/TPUv4 paper solved with expert judgment — closed here with the
compiler's own cost model:

1. **Enumerate** (:mod:`candidates`): every mesh factorization x
   parallelism strategy (data / fsdp / zero1 / tensor / expert / pipe
   and their products) valid for the family, device count, and global
   batch. Hard constraints — batch divisibility over the data axis
   (the SAME rule the elastic supervisor applies,
   parallel.mesh.pick_data_width/mesh_infeasible), head divisibility
   over "model", expert divisibility over "expert", layer/microbatch
   divisibility over "pipe" — prune up front, each with its reason
   recorded.
2. **Score** (:mod:`score`): for each survivor, build the REAL jitted
   train step (the same train/step.py / train/pipeline_step.py
   builders the loop uses) over a sharding-annotated ABSTRACT state
   (train.state.abstract_train_state — zero bytes allocated),
   ``lower()+compile()`` it WITHOUT executing, and read XLA's own
   ``cost_analysis``/``memory_analysis`` through the same extraction
   the compiled-program registry uses (observe.device.extract_costs).
   Predicted step time is a roofline:
   ``max(flops/peak_flops, bytes/hbm_bw) + collective_bytes/ici_bw``
   with the collective traffic censused from the program's jaxpr
   (analysis.jaxprcheck's walk). Candidates whose peak-HBM estimate
   exceeds the budget are MARKED infeasible, never silently dropped.
3. **Emit** (:mod:`plan`): a ranked table + ``plan.json``::

       python -m tensorflow_distributed_tpu.analysis.planner \
           --family gpt --devices 8 --batch-size 128

   and ``--plan auto`` on the train CLI, which runs the same search
   and launches with the winner's ``--mesh.*``/``--param-partition``
   config, recording a ``plan`` JSONL record through observe so the
   choice is auditable (observe.report renders it as the "Plan"
   section).

Its picks have only ever been compared with executed candidates on a
CPU at tiny size; no cell runs it (ROADMAP.md C7 puts it on trial).
"""

from tensorflow_distributed_tpu.analysis.planner.candidates import (  # noqa: F401
    Candidate, ModelFacts, enumerate_candidates, model_facts)
from tensorflow_distributed_tpu.analysis.planner.plan import (  # noqa: F401
    apply_auto, load_plan, make_plan, render_table, write_plan)
from tensorflow_distributed_tpu.analysis.planner.score import (  # noqa: F401
    Hardware, detect_hardware, mark_feasibility, roofline_ms,
    score_candidates)
