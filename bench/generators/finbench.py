"""LDBC FinBench transaction-shaped graph.

Account transfer rings (Zipf offsets, so transfers cluster), Person and
Company own Account, Person workIn Company (40% of persons), Person or
Company apply Loan (70% by a person), Person guarantee Company, Loan
deposit Account.
"""
from __future__ import annotations

import numpy as np

from bench.lib.graphgen import BaseGraph, Builder, exact_subset, ring_offsets


def generate(seed: int, n_account: int, n_person: int, n_company: int,
             n_loan: int, transfer_deg: float, **_) -> BaseGraph:
    rng = np.random.default_rng(seed)
    b = Builder()
    accounts = b.nodes("Account", n_account)
    persons = b.nodes("Person", n_person)
    companies = b.nodes("Company", n_company)
    loans = b.nodes("Loan", n_loan)
    n_tr = int(n_account * transfer_deg)
    src = rng.integers(0, n_account, n_tr)
    dst = (src + ring_offsets(rng, 1, n_tr, n_account, 1.8)) % n_account
    b.edges(accounts[src], accounts[dst], "transfer")
    b.edges(persons, accounts[rng.integers(0, n_account, n_person)], "own")
    workers = exact_subset(rng, n_person, 0.4)
    b.edges(persons[workers],
            companies[rng.integers(0, n_company, workers.shape[0])],
            "workIn")
    b.edges(companies, accounts[rng.integers(0, n_account, n_company)],
            "own")
    by_person = np.zeros(n_loan, bool)
    by_person[exact_subset(rng, n_loan, 0.7)] = True
    b.edges(np.where(by_person, persons[rng.integers(0, n_person, n_loan)],
                     companies[rng.integers(0, n_company, n_loan)]),
            loans, "apply")
    b.edges(loans, accounts[rng.integers(0, n_account, n_loan)], "deposit")
    n_g = n_person // 3
    b.edges(persons[rng.integers(0, n_person, n_g)],
            companies[rng.integers(0, n_company, n_g)], "guarantee")
    return b.done()
