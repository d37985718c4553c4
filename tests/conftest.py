import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose -- smoke tests must see the single real
# CPU device; multi-device tests spawn subprocesses with their own flags.


@pytest.fixture(scope="session")
def figure1_db():
    """The paper's Figure-1 graph with deterministic extractors."""
    from repro.core import PandaDB
    from repro.core.aipm import feature_hash_extractor, label_extractor

    db = PandaDB()
    db.register_extractor("face", feature_hash_extractor(dim=64))
    db.register_extractor("animal", label_extractor(["cat", "dog", "bird"]))
    rng = np.random.default_rng(0)
    jordan = db.graph.create_node("Person", name="Michael Jordan",
                                  photo=rng.bytes(512))
    bulls = db.graph.create_node("Team", name="Chicago Bulls")
    pet = db.graph.create_node("Pet", name="Tom", photo=rng.bytes(512))
    pippen = db.graph.create_node("Person", name="Scott Pippen",
                                  photo=rng.bytes(512))
    kerr = db.graph.create_node("Person", name="Steve Kerr",
                                photo=rng.bytes(512))
    warriors = db.graph.create_node("Team", name="Golden State Warriors")
    db.graph.create_relationship(jordan, bulls, "workFor")
    db.graph.create_relationship(jordan, pet, "hasPet")
    db.graph.create_relationship(jordan, pippen, "teamMate")
    db.graph.create_relationship(jordan, kerr, "teamMate")
    db.graph.create_relationship(kerr, warriors, "coachOf")
    db._node_ids = dict(jordan=jordan, bulls=bulls, pet=pet, pippen=pippen,
                        kerr=kerr, warriors=warriors)
    return db


@pytest.fixture(scope="session")
def smoke_mesh():
    import jax
    from repro.launch.mesh import make_smoke_mesh
    return make_smoke_mesh()
