"""Index — a namespace of fields (index.go:27).

Tracks column existence in a hidden ``_exists`` field when
track_existence is on (index.go existenceFieldName), which backs
Not()/All() and column counts.
"""

from __future__ import annotations

import os
import threading

from pilosa_tpu.models.field import Field
from pilosa_tpu.models.schema import FieldOptions, FieldType
from pilosa_tpu.shardwidth import SHARD_WIDTH

EXISTENCE_FIELD = "_exists"


class Index:
    def __init__(self, name: str, keys: bool = False,
                 track_existence: bool = True, width: int = SHARD_WIDTH,
                 path: str | None = None):
        self.name = name
        self.keys = keys
        self.track_existence = track_existence
        self.width = width
        self.path = path
        self.fields: dict[str, Field] = {}
        self._lock = threading.RLock()
        self._column_translator = None
        self.storage = None
        self._dataframe = None
        if path is not None:
            from pilosa_tpu.storage.shards import IndexStorage
            self.storage = IndexStorage(path)
        if track_existence:
            self._ensure_existence()

    @property
    def dataframe(self):
        """Lazy per-index Arrow dataframe (apply.go / arrow.go;
        /index/{i}/dataframe route)."""
        with self._lock:  # two racing firsts must not double-create
            if self._dataframe is None:
                from pilosa_tpu.models.dataframe import IndexDataframe
                self._dataframe = IndexDataframe(self.path)
            return self._dataframe

    @property
    def column_translator(self):
        """Partitioned column-key translator (keys=True indexes)."""
        if not self.keys:
            return None
        with self._lock:
            if self._column_translator is None:
                from pilosa_tpu.storage.translate import PartitionedTranslator
                tpath = os.path.join(self.path, "_keys") if self.path else None
                self._column_translator = PartitionedTranslator(
                    self.name, tpath, shard_width=self.width)
            return self._column_translator

    def _ensure_existence(self) -> Field:
        f = self.fields.get(EXISTENCE_FIELD)
        if f is None:
            f = Field(self.name, EXISTENCE_FIELD,
                      FieldOptions(type=FieldType.SET), self.width,
                      storage=self.storage)
            self.fields[EXISTENCE_FIELD] = f
        return f

    def _field_path(self, name: str) -> str | None:
        return os.path.join(self.path, "fields", name) if self.path else None

    def create_field(self, name: str, options: FieldOptions | None = None,
                     ok_if_exists: bool = False) -> Field:
        with self._lock:
            if name in self.fields:
                if ok_if_exists or name == EXISTENCE_FIELD:
                    return self.fields[name]
                raise ValueError(f"field already exists: {name}")
            f = Field(self.name, name, options, self.width,
                      path=self._field_path(name), storage=self.storage)
            self.fields[name] = f
            return f

    def field(self, name: str) -> Field | None:
        return self.fields.get(name)

    def clone_to(self, dst: "Index") -> None:
        """Deep-copy this index's schema, bitmaps (every view, so
        time-quantum placement survives), key translations, and BSI
        bookkeeping into `dst` (a fresh index created with the same
        `keys`).  Owns the write-path state transfer — bit_depth and
        observed extrema are not derivable from set_row_words alone —
        so callers (SQL COPY) never touch field internals."""
        import numpy as np
        if self.keys and self.column_translator is not None:
            # partition routing hashes the INDEX NAME (key_to_key_
            # partition / shard_to_shard_partition), so entries must
            # re-partition under dst's name — and into BOTH the
            # key-hash store (forward lookups) and the shard-owner
            # store (reverse lookups) when those differ, which keeps
            # each store's max-id tracking collision-safe for future
            # allocations
            from pilosa_tpu.storage.translate import (
                key_to_key_partition,
                shard_to_shard_partition,
            )
            ct = dst.column_translator
            src_ct = self.column_translator
            # nonempty_partitions scans keys.*.jsonl on disk too —
            # _stores alone misses partitions not yet lazily opened
            # (e.g. right after a Holder reopen)
            for _p in src_ct.nonempty_partitions():
                store = src_ct._store(_p)
                for i, k in store.entries():
                    fwd = key_to_key_partition(dst.name, k,
                                               ct.partition_n)
                    rev = shard_to_shard_partition(
                        dst.name, i // ct.shard_width, ct.partition_n)
                    ct._store(fwd).force_set(i, k)
                    if rev != fwd:
                        ct._store(rev).force_set(i, k)

        def copy_field(f, nf):
            nf.bit_depth = f.bit_depth
            nf._min_seen = f._min_seen
            nf._max_seen = f._max_seen
            if f.row_translator is not None and \
                    nf.row_translator is not None:
                nf.row_translator.restore_snapshot(
                    f.row_translator.snapshot())
            for vn, v in f.views.items():
                nv = nf.view(vn, create=True)
                for shard, frag in v.fragments.items():
                    nfrag = nv.fragment(shard, create=True)
                    for r in frag.row_ids:
                        nfrag.set_row_words(
                            r, np.array(frag.row_words(r)))

        for f in self.public_fields():
            copy_field(f, dst.create_field(f.name, f.options))
        ef = self.fields.get(EXISTENCE_FIELD)
        if ef is not None:
            copy_field(ef, dst._ensure_existence())

    def rename_field(self, old: str, new: str):
        """ALTER TABLE .. RENAME COLUMN old TO new (sql3/planner/
        compilealtertable.go): renames the field in the schema, moves
        its key-translator directory, and rewrites its persisted
        bitmaps under the new name."""
        from pilosa_tpu.models.view import bsi_view_name
        with self._lock:
            f = self.fields.get(old)
            if f is None:
                raise ValueError(f"field not found: {old}")
            if new in self.fields or new == EXISTENCE_FIELD:
                raise ValueError(f"field already exists: {new}")
            del self.fields[old]
            f.name = new
            self.fields[new] = f
            # move the key-translator dir; open handles survive a
            # POSIX rename
            oldp, newp = self._field_path(old), self._field_path(new)
            if oldp and os.path.isdir(oldp):
                os.rename(oldp, newp)
            if f.path:
                f.path = newp
            old_bsi, new_bsi = bsi_view_name(old), bsi_view_name(new)
            for vn in list(f.views):
                v = f.views[vn]
                v.field_name = new
                nvn = new_bsi if vn == old_bsi else vn
                for frag in v.fragments.values():
                    frag.field_name = new
                    frag.view_name = nvn
                    # rewrite every row under the new bitmap name
                    frag.dirty_rows.update(frag._rows)
                    frag.dirty_rows.update(frag._sparse)
                    frag.dirty_rows.update(frag.row_ids)
                if nvn != vn:
                    v.name = nvn
                    f.views[nvn] = f.views.pop(vn)
        if self.storage is not None:
            self.sync()
            self.storage.delete_field_bitmaps(old)

    def delete_field(self, name: str):
        with self._lock:
            f = self.fields.pop(name, None)
            if f is None:
                return
            # deletion changes read results without any fragment
            # touch(): cached fused programs must observe it
            from pilosa_tpu.models.fragment import bump_mutation_epoch
            bump_mutation_epoch()
            if self.storage is not None:
                self.storage.delete_field_bitmaps(name)
            # drop the field's key-translator files too, or a recreated
            # field would inherit the old key->row mappings
            f.close()
            fp = self._field_path(name)
            if fp and os.path.isdir(fp):
                import shutil
                shutil.rmtree(fp)

    # -- persistence -----------------------------------------------------

    def sync(self):
        """Persist dirty fragment rows, one write tx per shard file."""
        if self._dataframe is not None:
            self._dataframe.sync()
        if self.storage is None:
            return
        with self._lock:
            by_shard: dict[int, list] = {}
            for f in self.fields.values():
                for v in f.views.values():
                    for frag in v.fragments.values():
                        if frag.dirty_rows:
                            by_shard.setdefault(frag.shard, []).append(frag)
            for shard in sorted(by_shard):
                self.storage.write_fragments(by_shard[shard])

    def load_fragments(self):
        """Materialize every fragment present on disk (holder open)."""
        if self.storage is None:
            return
        with self._lock:
            for fname, vname, shard in self.storage.discover():
                f = self.fields.get(fname)
                if f is None:
                    continue  # bitmap for a dropped/unknown field
                frag = f.view(vname, create=True).fragment(shard, create=True)
                if f.options.type.is_bsi:
                    # recover observed bit depth from the stored planes
                    from pilosa_tpu.shardwidth import BSI_OFFSET_BIT
                    depth = frag.max_row_id() - BSI_OFFSET_BIT + 1
                    if depth > f.bit_depth:
                        f.bit_depth = depth

    def close(self):
        if self.storage is not None:
            self.storage.close()
        if self._column_translator is not None:
            self._column_translator.close()
        for f in self.fields.values():
            f.close()

    def public_fields(self) -> list[Field]:
        return [f for n, f in sorted(self.fields.items())
                if n != EXISTENCE_FIELD]

    def mark_columns_exist(self, cols):
        if not self.track_existence:
            return
        import numpy as np
        f = self._ensure_existence()
        f.import_bits(np.zeros(len(cols), dtype=np.int64), cols)

    def existence_row(self, shard: int):
        """Packed existence words for a shard (or None if untracked)."""
        f = self.fields.get(EXISTENCE_FIELD)
        if f is None:
            return None
        v = f.views.get("standard")
        frag = v.fragment(shard) if v else None
        return frag.row_words(0) if frag else None

    @property
    def available_shards(self) -> set[int]:
        s: set[int] = set()
        for f in self.fields.values():
            s.update(f.available_shards)
        return s

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "options": {"keys": self.keys,
                        "trackExistence": self.track_existence},
            "fields": [f.to_dict() for f in self.public_fields()],
        }
