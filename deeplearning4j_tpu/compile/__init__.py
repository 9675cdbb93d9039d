"""Persistent compile layer + schedule autotuner (ROADMAP item 2).

Two ideas from TVM (PAPERS.md, arXiv 1802.04799), applied to this
framework's own config space:

* `persistent` — compiled executables as the persisted, shippable unit:
  an on-disk `PersistentExecutableCache` keyed by (environment, topology,
  model fingerprint, argument signature) with crc-checked atomic writes,
  so new processes (serving scale-out replicas, preempted-trainer
  restarts, bench runs) deserialize instead of recompiling.
* `autotune` — learned schedule search over {fused_steps, prefetch depth,
  zero1, donation, bucket ladder}, persisted as a JSON artifact next to
  the executable store and re-applied at build time via
  `load_schedule()`.

Opt-in: nothing persists unless a cache directory is configured — pass
`cache=`/`cache_dir=` explicitly, call `set_default_cache(dir)`, or set
`$DL4J_TPU_EXEC_CACHE`.
"""
from deeplearning4j_tpu.compile.autotune import (  # noqa: F401
    DEFAULT_SPACE, Schedule, ScheduleAutotuner, TileAutotuner,
    autotune_tiles, load_schedule, load_tile_table, save_schedule,
    save_tile_entry, schedule_path, tile_table_path)
from deeplearning4j_tpu.compile.fingerprint import (  # noqa: F401
    environment_fingerprint, kernel_tier_fingerprint, mesh_fingerprint,
    model_fingerprint, transform_fingerprint)
from deeplearning4j_tpu.compile.persistent import (  # noqa: F401
    PersistentExecutableCache, as_cache, default_cache, default_cache_dir,
    place_compilation_cache, set_default_cache)
from deeplearning4j_tpu.compile.step_cache import (  # noqa: F401
    AotStepFunction, step_function)
