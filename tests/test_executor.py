"""End-to-end query execution over the Figure-1 graph."""
import numpy as np
import pytest

from repro.core.executor import ExecutionContext, execute


def q(db, text, optimized=True):
    return db.query(text, optimized=optimized)


def test_teammate_query(figure1_db):
    rows = q(figure1_db,
             "MATCH (n:Person)-[:teamMate]->(m:Person) "
             "WHERE n.name='Michael Jordan' RETURN m.name")
    names = {r["m.name"] for r in rows}
    assert names == {"Scott Pippen", "Steve Kerr"}


def test_incoming_direction(figure1_db):
    rows = q(figure1_db,
             "MATCH (m:Person)<-[:teamMate]-(n:Person) "
             "WHERE n.name='Michael Jordan' RETURN m.name")
    assert {r["m.name"] for r in rows} == {"Scott Pippen", "Steve Kerr"}


def test_two_hop(figure1_db):
    rows = q(figure1_db,
             "MATCH (n:Person)-[:teamMate]->(m:Person)-[:coachOf]->(t:Team) "
             "WHERE n.name='Michael Jordan' RETURN m.name, t.name")
    assert rows == [{"m.name": "Steve Kerr",
                     "t.name": "Golden State Warriors"}]


def test_semantic_label_filter(figure1_db):
    rows = q(figure1_db,
             "MATCH (n:Person)-[:hasPet]->(p:Pet) "
             "WHERE n.name='Michael Jordan' AND p.photo->animal='dog' "
             "RETURN p.name")
    rows_cat = q(figure1_db,
                 "MATCH (n:Person)-[:hasPet]->(p:Pet) "
                 "WHERE n.name='Michael Jordan' AND p.photo->animal='cat' "
                 "RETURN p.name")
    # deterministic extractor assigns exactly one label
    assert (len(rows) == 1) != (len(rows_cat) == 1)


def test_face_self_similarity(figure1_db):
    rows = q(figure1_db,
             "MATCH (n:Person) WHERE n.photo->face ~: n.photo->face "
             "RETURN n.name")
    assert len(rows) == 3  # every Person with a photo is similar to itself


def test_q3_same_person(figure1_db):
    """Paper Q3: is Jordan's former teammate Kerr the Warriors' coach?"""
    rows = q(figure1_db,
             "MATCH (n:Person)-[:teamMate]->(m:Person), "
             "(c:Person)-[:coachOf]->(t:Team) "
             "WHERE n.name='Michael Jordan' AND t.name='Golden State Warriors'"
             " AND m.photo->face ~: c.photo->face RETURN m.name")
    assert {r["m.name"] for r in rows} == {"Steve Kerr"}


def test_numeric_comparison(figure1_db):
    db = figure1_db
    db.graph.store.node_props.set(db._node_ids["jordan"], "age", 60.0)
    db.graph.store.node_props.set(db._node_ids["kerr"], "age", 58.0)
    rows = q(db, "MATCH (n:Person) WHERE n.age > 59 RETURN n.name")
    assert {r["n.name"] for r in rows} == {"Michael Jordan"}


def test_optimized_and_naive_agree(figure1_db):
    text = ("MATCH (n:Person)-[:teamMate]->(m:Person) "
            "WHERE n.name='Michael Jordan' AND m.photo->face ~: m.photo->face "
            "RETURN m.name")
    a = {r["m.name"] for r in q(figure1_db, text, optimized=True)}
    b = {r["m.name"] for r in q(figure1_db, text, optimized=False)}
    assert a == b


def test_limit(figure1_db):
    rows = q(figure1_db, "MATCH (n:Person) RETURN n.name LIMIT 2")
    assert len(rows) == 2


def test_create_via_query():
    from repro.core import PandaDB
    db = PandaDB()
    db.query("CREATE (a:Person {name: 'X'}) CREATE (b:Person {name: 'Y'}) "
             "CREATE (a)-[:knows]->(b)")
    rows = db.query("MATCH (a:Person)-[:knows]->(b:Person) "
                    "WHERE a.name='X' RETURN b.name")
    assert rows == [{"b.name": "Y"}]
    assert db.graph.wal.version == 1   # one writing-query logged


def test_extract_count_optimized_vs_naive(figure1_db):
    """The optimizer's whole point: fewer φ invocations (paper Fig 9/10)."""
    from repro.core.executor import ExecutionContext, execute
    db = figure1_db
    text = ("MATCH (n:Person)-[:hasPet]->(p:Pet) "
            "WHERE n.name='Michael Jordan' AND p.photo->animal='cat' "
            "RETURN p.name")
    db.cache.clear()
    ctx1 = ExecutionContext(db)
    execute(db.plan(text, optimized=True), ctx1)
    db.cache.clear()
    ctx2 = ExecutionContext(db)
    execute(db.plan(text, optimized=False), ctx2)
    assert ctx1.extract_count <= ctx2.extract_count


# ---------------------------------------------------------------------------
# batched vector-index pushdown (var-var similarity)
# ---------------------------------------------------------------------------


def _face_db(n=40, seed=11):
    from repro.core import PandaDB
    from repro.core.aipm import feature_hash_extractor
    db = PandaDB()
    db.register_extractor("face", feature_hash_extractor(dim=32))
    rng = np.random.default_rng(seed)
    photos = [rng.bytes(256) for _ in range(n // 2)]
    for i in range(n):
        # pairs share a photo -> guaranteed cross-var similarity matches
        db.graph.create_node("Person", name=f"p_{i}", photo=photos[i // 2])
    for i in range(0, n - 1, 2):
        db.graph.create_relationship(i, i + 1, "knows")
    return db


def test_var_var_pushdown_matches_extraction_path():
    """`a.photo->face ~: b.photo->face` with an index on face: per-row query
    vectors batch into one search_many per chunk, same rows as the
    extract-both-sides path."""
    text = ("MATCH (a:Person)-[:knows]->(b:Person) "
            "WHERE a.photo->face ~: b.photo->face RETURN a.name, b.name")
    db = _face_db()
    baseline = {tuple(sorted(r.items())) for r in db.query(text)}
    assert len(baseline) > 0
    db2 = _face_db()
    db2.build_index("face", "photo")
    ctx = ExecutionContext(db2)
    _, rows = execute(db2.plan(text), ctx)
    pushed = {tuple(sorted(r.items())) for r in rows}
    assert ctx.index_hits >= 1
    assert pushed == baseline


def test_var_var_pushdown_queries_from_the_anchor_side():
    """A one-row anchor joined against a whole label (`n.name = ... AND
    n.photo->face ~: m.photo->face`) searches the index once, from the
    anchor's photo, across every chunk -- same rows as extraction."""
    text = ("MATCH (n:Person), (m:Person) WHERE n.name = 'p_2' "
            "AND n.photo->face ~: m.photo->face RETURN m.name")
    baseline = {r["m.name"] for r in _face_db().query(text)}
    assert baseline == {"p_2", "p_3"}
    db = _face_db()
    index = db.build_index("face", "photo")
    calls = []
    search = index.search_many
    index.search_many = lambda q, *a, **kw: (calls.append(len(q)),
                                             search(q, *a, **kw))[1]
    rows = db.session(batch_rows=8).run(text).fetchall()   # several chunks
    assert {r["m.name"] for r in rows} == baseline
    assert calls == [1]


def test_self_similarity_pushdown_short_circuits():
    """`x ~: x` with an index: rows with a blob pass without any search."""
    db = _face_db(20)
    db.build_index("face", "photo")
    db.cache.clear()
    ctx = ExecutionContext(db)
    _, rows = execute(db.plan(
        "MATCH (p:Person) WHERE p.photo->face ~: p.photo->face "
        "RETURN p.name"), ctx)
    assert len(rows) == 20
    assert ctx.index_hits >= 1
    assert ctx.extract_count == 0     # neither side extracted per row
