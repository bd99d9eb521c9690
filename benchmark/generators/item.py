"""``item``: the spec's 22 columns, uniform ids."""

import numpy as np
import pandas as pd

from benchmark.lib import datagen as D

STREAM = 1          # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``


def make(rng, rows, made) -> pd.DataFrame:
    n = rows["item"]
    sk = np.arange(1, n + 1)
    cat_id = rng.integers(1, 11, n)
    class_id = ((cat_id - 1) * 3 + rng.integers(1, 11, n) % 3) % 10 + 1
    manufact = rng.integers(1, 101, n)
    brand_id = cat_id * 1000000 + class_id * 10000 + rng.integers(1, 100, n)
    manager = rng.integers(1, 101, n)
    return pd.DataFrame({
        "i_item_sk": sk.astype(np.int64),
        "i_item_id": D.ids(sk),
        "i_rec_start_date": "1997-10-27", "i_rec_end_date": None,
        "i_item_desc": [f"item description {x}" for x in sk],
        "i_current_price": np.round(rng.uniform(0.5, 100.0, n), 2),
        "i_wholesale_cost": np.round(rng.uniform(0.3, 80.0, n), 2),
        "i_brand_id": brand_id.astype(np.int32),
        "i_brand": [f"brand#{b}" for b in brand_id],
        "i_class_id": class_id.astype(np.int32),
        "i_class": [D.CLASSES[c - 1] for c in class_id],
        "i_category_id": cat_id.astype(np.int32),
        "i_category": [D.CATEGORIES[c - 1] for c in cat_id],
        "i_manufact_id": manufact.astype(np.int32),
        "i_manufact": [f"manufact#{m}" for m in manufact],
        "i_size": rng.choice(["small", "medium", "large", "extra large",
                              "economy", "N/A", "petite"], n),
        "i_formulation": [f"formulation {x}" for x in rng.integers(0, 100, n)],
        "i_color": rng.choice(["red", "blue", "green", "white", "black",
                               "navy", "peru", "saddle", "powder"], n),
        "i_units": rng.choice(["Each", "Dozen", "Case", "Pallet", "Oz",
                               "Lb", "Ton", "Gram"], n),
        "i_container": "Unknown",
        "i_manager_id": manager.astype(np.int32),
        "i_product_name": [f"product {x}" for x in sk],
    })
