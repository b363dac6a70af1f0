"""TPC-H lineitem loaded into the system as for ``tpch_lineitem_sf10``,
and the Q6 family as the predicates a trainer pulls its rows by."""
from __future__ import annotations

from chipbench.configs.tpch_lineitem_sf10 import plan, query

__all__ = ["plan", "where"]


def where(name: str, p: dict):
    """The predicate of a query with its parameters: the rows it matches
    are the rows served."""
    return query(name, p)[0]
