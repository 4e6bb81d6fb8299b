"""Backend routing for the engine: cost model + cached autotuner.

``impl="auto"``  → :func:`repro.tuning.costmodel.choose_plan` — pure
arithmetic over an analytical per-hop cost model; safe on the hot path.
``impl="tuned"`` → :func:`repro.tuning.autotune.autotune` — times a
cost-model shortlist on the actual model/batch shape, caches the winner
per (shape, device fingerprint).

Every plan is a pure *execution* choice: all backends are bit-identical
(``docs/PARITY.md``), so routing can only change speed, never verdicts.
"""
from repro.tuning.autotune import (  # noqa: F401
    autotune,
    cache_path,
    device_fingerprint,
    get_plan,
    load_cache,
    save_cache,
    time_plan,
)
from repro.tuning.costmodel import (  # noqa: F401
    BACKENDS,
    BLOCK_B_CANDIDATES,
    Coefficients,
    Plan,
    ShapeInfo,
    calibrate,
    candidate_plans,
    choose_plan,
    estimate_us,
    fit_coefficients,
    work_terms,
)
