"""Property tests for the persistent AOT artifact store and the bundle-key
function: round-trip byte determinism over arbitrary blob sets, bundle-key
injectivity under random field perturbations, crash/corruption safety (a
mangled artifact is a MISS, never an exception — the boot ladder depends on
``get()`` never raising), and put/get consistency under concurrent writers.

Module requires `hypothesis` (skip-guarded in conftest.py like the other
property suites). The store is pure host-side stdlib — no jax arrays — so
examples are cheap; each example builds its own store in a fresh temp dir
(no function-scoped pytest fixtures inside ``@given``, per hypothesis'
health check)."""
import json
import os
import tempfile
import threading

from hypothesis import given, settings, strategies as st

from repro.checkpoint.store import ArtifactStore
from repro.core import aot

# blob names exercise the sanitizer: path-hostile characters must land as
# flat files under blobs/ and round-trip by their ORIGINAL name
_names = st.text(
    st.characters(codec="ascii", exclude_characters="\x00"),
    min_size=1, max_size=24)
_blobs = st.dictionaries(_names, st.binary(min_size=0, max_size=256),
                         min_size=1, max_size=8)

# bundle-key fields: the kinds of values the engine actually keys on
# (strings, ints, None, tuples-of-pairs like a tier fingerprint)
_field_vals = st.one_of(
    st.none(), st.booleans(), st.integers(-8, 8),
    st.text(max_size=8),
    st.tuples(st.text(max_size=4), st.text(max_size=4)))
_fields = st.fixed_dictionaries(
    {"family": st.sampled_from(["serving:a", "serving:b"]),
     "slots": st.integers(1, 4), "max_len": st.integers(8, 64),
     "tiers": _field_vals, "spec": _field_vals})


@given(_blobs, st.dictionaries(st.text(max_size=8), st.integers(),
                               max_size=4))
@settings(max_examples=40, deadline=None)
def test_roundtrip_byte_identity(blobs, meta):
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(d)
        store.put("k", blobs, meta=meta)
        got = store.get("k")
        assert got is not None
        out, out_meta = got
        assert out == blobs
        assert out_meta == meta
        # a second put of the same key atomically replaces, never corrupts
        store.put("k", blobs, meta=meta)
        assert store.get("k") == (blobs, meta)


@given(_fields, _fields)
@settings(max_examples=60, deadline=None)
def test_bundle_key_injective_over_fields(a, b):
    ka, kb = aot.bundle_key(a), aot.bundle_key(b)
    # repr, not ==: the key tells False from 0 and True from 1, as == does not
    assert (ka == kb) == (repr(a) == repr(b))
    assert ka.startswith("aot-")
    # deterministic: same fields, same key, every time
    assert ka == aot.bundle_key(dict(a))


@given(_blobs, st.data())
@settings(max_examples=40, deadline=None)
def test_corruption_is_a_miss_never_an_exception(blobs, data):
    """Truncate / overwrite / delete any committed file: get() must return
    None with a reason in ``last_error``, and the store must stay usable."""
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(d)
        store.put("k", blobs, meta={"n": len(blobs)})
        # find the artifact dir on disk without relying on private helpers
        art_dir = next(p for p in (os.path.join(d, e) for e in os.listdir(d))
                       if os.path.isdir(p))
        files = sorted(
            os.path.join(dp, f)
            for dp, _, fs in os.walk(art_dir) for f in fs)
        victim = files[data.draw(st.integers(0, len(files) - 1))]
        action = data.draw(st.sampled_from(["truncate", "garbage", "delete"]))
        if action == "truncate":
            with open(victim, "rb") as f:
                raw = f.read()
            with open(victim, "wb") as f:
                f.write(raw[: len(raw) // 2])
        elif action == "garbage":
            with open(victim, "wb") as f:
                f.write(b"\xde\xad\xbe\xef")
        else:
            os.remove(victim)

        got = store.get("k")
        if got is not None:
            # only legal survival: the mangled file did not participate in
            # the manifest's integrity domain AND bytes still verify
            out, _ = got
            assert out == blobs
        else:
            assert store.last_error
            assert store.stats["misses"] + store.stats["corrupt"] >= 1
        # the store is still writable and consistent after the damage
        store.put("k2", blobs, meta={})
        assert store.get("k2") == (blobs, {})


@given(st.lists(_blobs, min_size=2, max_size=4))
@settings(max_examples=15, deadline=None)
def test_concurrent_put_get_consistency(blob_sets):
    """N writers hammer the SAME key while readers poll: every successful
    read must be one of the complete bundles, never an interleaving."""
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(d)
        valid = [frozenset((k, v) for k, v in b.items()) for b in blob_sets]
        errors: list[str] = []

        def writer(b):
            for _ in range(3):
                store.put("k", b, meta={})

        def reader():
            for _ in range(10):
                got = store.get("k")
                if got is None:
                    continue
                seen = frozenset((k, v) for k, v in got[0].items())
                if seen not in valid:
                    errors.append(f"torn read: {sorted(got[0])}")

        threads = [threading.Thread(target=writer, args=(b,))
                   for b in blob_sets]
        threads += [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        got = store.get("k")
        assert got is not None
        assert frozenset((k, v) for k, v in got[0].items()) in valid


@given(_blobs)
@settings(max_examples=20, deadline=None)
def test_manifest_records_every_blob(blobs):
    """The on-disk MANIFEST.json is the integrity domain: one entry per
    blob with its byte length and sha256 (what the corrupt-boot test in
    test_ir_boot.py relies on)."""
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(d)
        store.put("k", blobs, meta={})
        art_dir = next(p for p in (os.path.join(d, e) for e in os.listdir(d))
                       if os.path.isdir(p))
        man = json.load(open(os.path.join(art_dir, "MANIFEST.json")))
        entries = man["blobs"]
        names = {e["name"] for e in entries}
        assert names == set(blobs)
        for e in entries:
            assert e["bytes"] == len(blobs[e["name"]])
            assert len(e["sha256"]) == 64
