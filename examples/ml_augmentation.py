"""Factorized-ML augmentation (paper §4.3 / Fig 18): train a linear model
over the Favorita join, then evaluate 30 augmentation candidates at one
message each via the calibrated CJT.

    PYTHONPATH=src python examples/ml_augmentation.py
"""

import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import FactorizedLinearRegression, FeatureSpec
from repro.relational import schema


def main():
    cat = schema.favorita(n_sales=60_000)
    model = FactorizedLinearRegression(
        cat,
        features=[
            FeatureSpec("Sales", "unit_sales"),
            FeatureSpec("Stores", "store_type", categorical=True),
            FeatureSpec("Items", "perishable", categorical=True),
        ],
        target=FeatureSpec("Trans", "transactions"),
    )
    base = model.fit()
    print(f"base model: R2={base.r2:.4f}")

    t0 = time.perf_counter()
    model.calibrate()
    print(f"calibration: {time.perf_counter()-t0:.2f}s "
          f"(≈2× one factorized training, per the paper)")

    augs = schema.favorita_augmentations(cat, n_per_key=10)
    t0 = time.perf_counter()
    results = []
    for a in augs:
        r = model.fit_augmented(a)
        phi = float(a.measures["phi"][0])
        results.append((r.r2 - base.r2, phi, a.name, r.stats.messages_computed))
    dt = time.perf_counter() - t0
    print(f"evaluated {len(augs)} augmentations in {dt:.2f}s "
          f"({dt/len(augs)*1e3:.0f}ms each)")
    results.sort(reverse=True)
    print("top 5 augmentations (ΔR², φ, name, messages computed):")
    for dr2, phi, name, msgs in results[:5]:
        print(f"  {dr2:+.4f}  φ={phi:.2f}  {name}  msgs={msgs}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
