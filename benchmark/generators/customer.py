"""``customer``: the spec's 18 columns.  ``c_customer_id`` is the business
key (16 characters, unique a row) and ``c_email_address`` is unique a row
too, so each is a dictionary of ``rows["customer"]`` words; first and last
names come from about 5,000 synthetic words each (dsdgen draws from name
lists of that order, which are not here), some shared between the two
columns, with a Zipf-like weight so that a few names are common; 3.5% of the
rows lack a first name and another 3.5% a last name (dsdgen leaves some
blank).  The words themselves are drawn from the seed: another seed is
another dictionary."""

import numpy as np
import pandas as pd

from benchmark.lib import datagen as D

STREAM = 10         # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``

N_FIRST, N_LAST, N_SHARED = 5000, 5000, 1000
FIRST_LEN, LAST_LEN = (3, 11), (3, 13)
N_COUNTRIES = 200
NULL_NAME = 0.035           # of the rows, for each of the two name columns
SALUTATIONS = ["Mr.", "Mrs.", "Ms.", "Miss", "Dr.", "Sir"]
_LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


def words(rng, n, lengths, taken=()) -> list:
    """``n`` distinct capitalised words of ``lengths`` (lo, hi) letters,
    none of them in ``taken``."""
    lo, hi = lengths
    out, seen = [], set(taken)
    while len(out) < n:
        size = rng.integers(lo, hi + 1, n)
        letters = _LETTERS[rng.integers(0, 26, (n, hi))]
        for k, row in zip(size, letters):
            w = "".join(row[:k]).capitalize()
            if w not in seen and len(out) < n:
                seen.add(w)
                out.append(w)
    return out


def zipf_like(n) -> np.ndarray:
    """Weights 1 / (rank + 50): the commonest of 5,000 names is about one
    row in 230, the rarest one in 23,000."""
    w = 1.0 / (np.arange(n) + 50.0)
    return w / w.sum()


def make(rng, rows, made) -> pd.DataFrame:
    n = rows["customer"]
    sk = np.arange(1, n + 1)
    first_words = words(rng, N_FIRST, FIRST_LEN)
    last_words = first_words[:N_SHARED] + words(
        rng, N_LAST - N_SHARED, LAST_LEN, taken=first_words)
    rng.shuffle(last_words)
    first = np.array(first_words, object)[
        rng.choice(N_FIRST, n, p=zipf_like(N_FIRST))]
    last = np.array(last_words, object)[
        rng.choice(N_LAST, n, p=zipf_like(N_LAST))]
    blank = rng.random(n)
    first[blank < NULL_NAME] = None
    last[(blank >= NULL_NAME) & (blank < 2 * NULL_NAME)] = None
    countries = [w.upper() for w in words(rng, N_COUNTRIES, (4, 12))]
    first_sale = D.DATE0_SK - rng.integers(1, 3650, n)
    review = np.datetime64("2002-01-01") + rng.integers(0, 365, n)

    def key(size):
        return rng.integers(1, size + 1, n).astype(np.int64)

    return pd.DataFrame({
        "c_customer_sk": sk.astype(np.int64),
        "c_customer_id": D.ids(sk),
        "c_current_cdemo_sk": key(rows["customer_demographics"]),
        "c_current_hdemo_sk": key(rows["household_demographics"]),
        "c_current_addr_sk": key(rows["customer_address"]),
        "c_first_shipto_date_sk": (first_sale + rng.integers(0, 30, n))
        .astype(np.int64),
        "c_first_sales_date_sk": first_sale.astype(np.int64),
        "c_salutation": rng.choice(SALUTATIONS, n),
        "c_first_name": first, "c_last_name": last,
        "c_preferred_cust_flag": rng.choice(["Y", "N"], n),
        "c_birth_day": rng.integers(1, 29, n).astype(np.int32),
        "c_birth_month": rng.integers(1, 13, n).astype(np.int32),
        "c_birth_year": rng.integers(1924, 1993, n).astype(np.int32),
        "c_birth_country": rng.choice(countries, n),
        "c_login": None,
        "c_email_address": [f"c{x:08d}@mail{x % 97}.example.com"
                            for x in sk],
        "c_last_review_date": review.astype(str),
    })
