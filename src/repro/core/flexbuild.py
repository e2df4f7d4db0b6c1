"""flexbuild — compose a deployment from LEGO-brick components (paper §3).

The paper's flexbuild selects components ①–㉔ and builds binaries/images;
here it validates GRIN trait compatibility and wires the selected storage,
engines, interfaces and model backends into one :class:`Deployment` object.
Incompatible combinations fail at *build* time (trait mismatch), not at
query time — the bricks refuse to interlock, which is the point.

Component ids follow Figure 3 of the paper (full bricks table and the
three composition rules: DESIGN.md §3):
  ③ gremlin  ④ cypher      ⑤ builtin-analytics  ⑦ gnn-models
  ⑫ hiactor  ⑬ gaia        ⑭ pie ⑮ flash ⑯ grape  ⑰ graphlearn
  ㉑ vineyard(csr) ㉒ gart  ㉓ graphar
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence

# the default brick selection for durability cold starts: both query
# interfaces plus the analytics engine. graphlearn is opt-in — its
# sampler binds a feature column eagerly, which a recovered store need
# not carry
DEFAULT_COMPONENTS = ("cypher", "gremlin", "grape")


def _open_durable(store, path: str, checkpoint_every: Optional[int],
                  checkpoint_keep: int):
    """Wrap/recover ``store`` through the durability tier at ``path``
    (DESIGN.md §16). An existing complete checkpoint wins: the store is
    recovered from disk (a passed ``store`` is only the bootstrap seed
    for an empty directory). A store already durable on this path is
    reused as-is."""
    from repro.storage.durability import open_durability
    from repro.storage.gart import GARTStore

    dur = getattr(store, "durability", None)
    if dur is not None:
        if os.path.abspath(dur.path) == os.path.abspath(path):
            return store
        raise ValueError(
            f"store is already durable on {dur.path!r}; refusing to "
            f"rebind it to {path!r}")
    if store is not None and not isinstance(store, GARTStore):
        raise TypeError(
            f"durability (path=...) needs a mutable GART store, got "
            f"{type(store).__name__}")
    kwargs = {"keep": checkpoint_keep}
    if checkpoint_every is not None:
        kwargs["checkpoint_every"] = checkpoint_every
    return open_durability(path, store, **kwargs)

from repro.storage.grin import (ANALYTICS_REQUIRED, GRINAdapter,
                                LEARNING_REQUIRED, QUERY_REQUIRED, Traits)

STORAGE_COMPONENTS = {"vineyard", "gart", "graphar"}
ENGINE_COMPONENTS = {"gaia", "hiactor", "grape", "graphlearn"}
INTERFACE_COMPONENTS = {"cypher", "gremlin", "pregel", "pie", "flash",
                        "sage", "ncn", "rgcn"}

ENGINE_TRAITS = {
    "gaia": QUERY_REQUIRED,
    "hiactor": QUERY_REQUIRED,
    "grape": ANALYTICS_REQUIRED,
    "graphlearn": LEARNING_REQUIRED,
}

INTERFACE_ENGINE = {
    "cypher": {"gaia", "hiactor"},
    "gremlin": {"gaia", "hiactor"},
    "pregel": {"grape"},
    "pie": {"grape"},
    "flash": {"grape"},
    "sage": {"graphlearn"},
    "ncn": {"graphlearn"},
    "rgcn": {"graphlearn"},
}


@dataclasses.dataclass
class Deployment:
    """A built stack: selected components wired over one storage backend."""

    store: Any
    components: List[str]
    engines: Dict[str, Any]
    n_frags: int = 1
    feature_prop: Optional[str] = None
    label_prop: Optional[str] = None

    def engine(self, name: str):
        return self.engines[name]

    def session(self, *, path: Optional[str] = None,
                checkpoint_every: Optional[int] = None,
                checkpoint_keep: int = 3, **kwargs):
        """The user-facing surface over this deployment: one
        :class:`~repro.serving.session.FlexSession` driving queries,
        writes, analytics and learning over the deployment's store
        (DESIGN.md §11). Keyword arguments override the session knobs
        (``n_frags``, ``feature_prop``, …) inherited from the build.

        ``path`` routes the store through the durability tier
        (DESIGN.md §16): an existing checkpoint under ``path`` recovers
        the pre-crash store (checkpoint + WAL-tail replay) and the
        deployment's in-memory store is ignored; an empty directory
        bootstraps it with an initial checkpoint. Every later commit is
        WAL-logged, auto-checkpointed every ``checkpoint_every`` commits
        and on ``session.close()``."""
        from repro.serving.session import FlexSession

        if path is not None:
            self.store = _open_durable(self.store, path,
                                       checkpoint_every, checkpoint_keep)
        kwargs.setdefault("n_frags", self.n_frags)
        if self.feature_prop is not None:
            kwargs.setdefault("feature_prop", self.feature_prop)
        if self.label_prop is not None:
            kwargs.setdefault("label_prop", self.label_prop)
        return FlexSession(self.store, **kwargs)

    def describe(self) -> str:
        lines = [f"storage: {type(self.store).__name__} "
                 f"(traits={self.store.traits()})"]
        for name, eng in self.engines.items():
            lines.append(f"engine: {name} -> {type(eng).__name__}")
        return "\n".join(lines)


def flexbuild(store=None, components: Optional[Sequence[str]] = None, *,
              path: Optional[str] = None,
              checkpoint_every: Optional[int] = None,
              checkpoint_keep: int = 3,
              mesh=None, n_frags: int = 1,
              feature_prop: Optional[str] = None,
              label_prop: Optional[str] = None,
              serve: bool = False, **session_kwargs):
    """Validate the selection and build the composed deployment.

    With ``serve=True`` the composed stack is returned as a ready
    :class:`~repro.serving.session.FlexSession` (the recommended surface:
    one façade over queries, writes, analytics and learning —
    DESIGN.md §11) instead of the loose-engine :class:`Deployment`;
    extra keyword arguments pass through to the session.

    ``path`` is the durability tier's front door (DESIGN.md §16):
    ``flexbuild(path=...)`` alone cold-starts from the newest complete
    checkpoint under it (WAL tail replayed — the crash-recovery path;
    ``components`` defaults to the full brick set), while
    ``flexbuild(store, comps, path=...)`` bootstraps a fresh durability
    directory around ``store``. Commits are WAL-logged write-ahead and
    auto-checkpointed every ``checkpoint_every`` commits, keeping the
    newest ``checkpoint_keep`` checkpoints."""
    if components is None:
        components = DEFAULT_COMPONENTS
    comps = list(components)
    if path is not None:
        store = _open_durable(store, path, checkpoint_every,
                              checkpoint_keep)
    elif checkpoint_every is not None:
        raise TypeError("checkpoint_every needs path= (a durability "
                        "directory to checkpoint into)")
    if store is None:
        raise TypeError("flexbuild needs a store, or path= pointing at "
                        "an existing durability directory to recover "
                        "from")
    unknown = [c for c in comps
               if c not in STORAGE_COMPONENTS | ENGINE_COMPONENTS
               | INTERFACE_COMPONENTS]
    if unknown:
        raise ValueError(f"unknown components: {unknown}")
    if session_kwargs and not serve:
        raise TypeError(f"unexpected arguments {sorted(session_kwargs)} "
                        f"(session knobs need serve=True)")
    if serve and mesh is not None:
        # the session's QueryService takes no mesh yet: refuse rather
        # than serve single-device while the caller asked for sharding
        raise TypeError("flexbuild(serve=True) cannot shard over a mesh "
                        "yet; build the loose Deployment (serve=False) "
                        "for sharded GRAPE analytics")

    # interfaces pull in their engines implicitly
    engines_wanted = {c for c in comps if c in ENGINE_COMPONENTS}
    for itf in comps:
        if itf in INTERFACE_ENGINE:
            if not engines_wanted & INTERFACE_ENGINE[itf]:
                engines_wanted.add(sorted(INTERFACE_ENGINE[itf])[0])

    # trait validation happens inside each engine's GRINAdapter; build them.
    # A mutable MVCC store interlocks through a *pinned snapshot* — loose
    # engines read one consistent version (the session rebinds on commit)
    eng_store = store
    t = store.traits()
    if (t & Traits.MUTABLE) and (t & Traits.MVCC_SNAPSHOT) \
            and hasattr(store, "snapshot"):
        eng_store = store.snapshot()
    dep = Deployment(store=store, components=comps, engines={},
                     n_frags=n_frags, feature_prop=feature_prop,
                     label_prop=label_prop)
    if serve:
        # the session builds (and rebinds) its own engines over its own
        # pinned snapshots — constructing the loose ones here would be
        # pure waste. Bricks still refuse to interlock at build time:
        # validate each selected engine's trait requirements now.
        for name in sorted(engines_wanted):
            GRINAdapter(eng_store, ENGINE_TRAITS[name])
        return dep.session(**session_kwargs)
    engines: Dict[str, Any] = {}
    for name in sorted(engines_wanted):
        if name == "grape":
            from repro.engines.grape import GrapeEngine
            engines[name] = GrapeEngine(eng_store, n_frags=n_frags, mesh=mesh)
        elif name == "gaia":
            from repro.engines.gaia import GaiaEngine
            engines[name] = GaiaEngine(eng_store)
        elif name == "hiactor":
            from repro.engines.hiactor import HiActorEngine
            engines[name] = HiActorEngine(eng_store)
        elif name == "graphlearn":
            from repro.learning.sampler import GraphSampler
            # rgcn's sampler draws relations and node types with the
            # neighbours, over the store's edge and vertex labels
            engines[name] = GraphSampler(eng_store,
                                         feature_prop=feature_prop or "feat",
                                         label_prop=label_prop,
                                         typed="rgcn" in comps)
    dep.engines = engines
    return dep
