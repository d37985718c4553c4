"""Numeric (B-tree role) + inverted semantic indexes (paper §VI-B2)."""
import numpy as np
import pytest

from repro.core import PandaDB
from repro.core.aipm import label_extractor
from repro.core.scalar_index import InvertedIndex, NumericIndex


def test_numeric_index_point_and_range():
    idx = NumericIndex.build([23.0, 45.0, 23.0, 7.0, 91.0],
                             [10, 11, 12, 13, 14])
    assert sorted(idx.eq(23.0).tolist()) == [10, 12]
    assert sorted(idx.range(lo=20, hi=50).tolist()) == [10, 11, 12]
    assert sorted(idx.range(hi=23, inclusive=False).tolist()) == [13]
    assert idx.eq(999.0).size == 0


def test_numeric_index_dynamic_insert():
    idx = NumericIndex.build([1.0, 5.0], [0, 1])
    idx.insert(3.0, 2)
    assert idx.keys.tolist() == [1.0, 3.0, 5.0]
    assert sorted(idx.range(lo=2, hi=4).tolist()) == [2]


def test_inverted_index_lookup():
    idx = InvertedIndex.build(["cat", "dog", "cat", "the tobacco leaf"],
                              [1, 2, 3, 4])
    assert sorted(idx.lookup("cat").tolist()) == [1, 3]
    assert idx.lookup("Tobacco").tolist() == [4]   # case-folded
    assert idx.lookup("missing").size == 0
    assert idx.posting("LEAF").ids.tolist() == [4]
    p = idx.posting("leaf")
    assert (p.owner, p.token) == ((idx.uid, 0), "leaf")


def test_inverted_index_dynamic_insert():
    idx = InvertedIndex.build(["cat"], [1])
    idx.insert("cat dog", 2)
    assert sorted(idx.lookup("cat").tolist()) == [1, 2]
    assert idx.lookup("dog").tolist() == [2]


@pytest.fixture()
def animal_db():
    db = PandaDB()
    db.register_extractor("animal", label_extractor(["cat", "dog", "bird"]))
    rng = np.random.default_rng(5)
    for i in range(30):
        db.graph.create_node("Pet", name=f"pet_{i}", photo=rng.bytes(256))
    return db


def test_scalar_index_pushdown_matches_unindexed(animal_db):
    db = animal_db
    text = "MATCH (p:Pet) WHERE p.photo->animal='cat' RETURN p.name"
    base = {r["p.name"] for r in db.query(text)}
    db.build_scalar_index("animal", "photo")
    assert "animal" in db.scalar_indexes

    from repro.core.executor import ExecutionContext, execute
    ctx = ExecutionContext(db)
    _, rows = execute(db.plan(text), ctx)
    assert ctx.index_hits == 1                 # pushdown fired
    assert {r["p.name"] for r in rows} == base
    # after pushdown the φ extraction count for this query is zero
    db.cache.clear()
    ctx2 = ExecutionContext(db)
    execute(db.plan(text), ctx2)
    assert ctx2.extract_count == 0


def test_scalar_index_invalidated_on_model_update(animal_db):
    db = animal_db
    db.build_scalar_index("animal", "photo")
    db.register_extractor("animal", label_extractor(["cat", "dog"], seed=9))
    assert "animal" not in db.scalar_indexes   # stale serial dropped
