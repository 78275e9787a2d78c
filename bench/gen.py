"""The deployment's data, made on the device from ``--seed``: HD codebooks,
the spectral library (targets and m/z-reversed decoys, bit-packed) and the
query pool.

This is the benchmark's own generator. It follows the synthetic peptide
model of ``repro.spectra.synthetic`` (sparse fragment-peak templates;
per-replicate intensity jitter, peak dropout and chemical-noise peaks;
open modifications that shift the upper half of the m/z axis and add a
precursor mass) but works on peak lists rather than dense spectra, and
draws each template's precursor uniformly over the configured range. It
shares no code with the program, so a change to the program cannot change
what is searched.

Every device program here takes the seed as a traced key, so one compile
serves every seed and every library pass.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 8192  # rows per generator/encoder step on the device
SET_TAG = 12  # stream of a traffic's ``set_seed``: its random batch set


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes a configuration file fixes (see ``bench/configs``)."""

    dim: int
    num_bins: int
    num_levels: int
    templates: int
    passes: int
    precursor_range: tuple[float, float]
    peaks_per_template: int
    noise_peaks: int
    intensity_jitter: float
    dropout: float
    precursor_noise: float
    modification_rate: float
    modification_mass: tuple[float, float]
    modification_bins: tuple[int, int]

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        a = cfg["assumed"]
        return cls(dim=cfg["dim"], num_bins=cfg["num_bins"],
                   num_levels=a["num_levels"], templates=cfg["templates"],
                   passes=cfg["replicate_passes"],
                   precursor_range=tuple(a["precursor_range"]),
                   peaks_per_template=a["peaks_per_template"],
                   noise_peaks=a["noise_peaks"],
                   intensity_jitter=a["intensity_jitter"],
                   dropout=a["peak_dropout"],
                   precursor_noise=a["precursor_noise"],
                   modification_rate=a["modification_rate"],
                   modification_mass=tuple(a["modification_mass"]),
                   modification_bins=tuple(a["modification_bins"]))

    @property
    def words(self) -> int:
        return self.dim // 32

    @property
    def library_rows(self) -> int:
        return self.templates * self.passes


def seed_key(seed: int, tag: int) -> jax.Array:
    """A raw threefry key for stream ``tag`` of ``seed`` (any integer that
    fits 64 bits, so seeds past 2**31 keep all their bits)."""
    s = int(seed) % (1 << 64)
    key = jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))
    return jax.random.fold_in(key, tag)


def level_thresholds(dim: int, num_levels: int) -> np.ndarray:
    """Level ``l`` flips dimensions ``[0, thresholds[l])`` of the base level
    vector: neighbouring levels are similar, the first and last orthogonal."""
    if dim // 2 < num_levels - 1:
        raise ValueError(f"dim {dim} too small for {num_levels} levels")
    return np.arange(num_levels) * (dim // 2) // (num_levels - 1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def codebooks(key, dim: int, num_bins: int, num_levels: int):
    """(id_hvs (F, D), level_hvs (m, D), base (D,)), bipolar int8."""
    k_id, k_base = jax.random.split(key)
    id_hvs = jax.random.rademacher(k_id, (num_bins, dim), dtype=jnp.int8)
    base = jax.random.rademacher(k_base, (dim,), dtype=jnp.int8)
    thr = jnp.asarray(level_thresholds(dim, num_levels))
    flip = jnp.arange(dim)[None, :] < thr[:, None]
    level_hvs = jnp.where(flip, -base[None, :], base[None, :]).astype(jnp.int8)
    return id_hvs, level_hvs, base


def _templates(key, prec_key, spec: Spec):
    """Peak positions and intensities of every template from ``key``, its
    precursor from ``prec_key``."""
    kp, ki, _ = jax.random.split(key, 3)
    km = jax.random.split(prec_key, 3)[2]
    shape = (spec.templates, spec.peaks_per_template)
    pos = jax.random.randint(kp, shape, 0, spec.num_bins, jnp.int32)
    inten = jax.random.uniform(ki, shape, minval=0.2, maxval=1.0)
    lo, hi = spec.precursor_range
    prec = jax.random.uniform(km, (spec.templates,), minval=lo, maxval=hi)
    return pos, inten, prec


def _replicate(key, tpos, tint, spec: Spec):
    """One observed spectrum per template row: intensity jitter, dropout,
    chemical-noise peaks. Returns peak lists (N, P + noise)."""
    n, p = tpos.shape
    kj, kd, kn, ki = jax.random.split(key, 4)
    jit = jnp.clip(1.0 + spec.intensity_jitter
                   * jax.random.normal(kj, (n, p)), 0.1, 2.0)
    keep = jax.random.uniform(kd, (n, p)) > spec.dropout
    inten = jnp.where(keep, tint * jit, 0.0)
    npos = jax.random.randint(kn, (n, spec.noise_peaks), 0, spec.num_bins,
                              jnp.int32)
    nint = jax.random.uniform(ki, (n, spec.noise_peaks), minval=0.05,
                              maxval=0.35)
    return (jnp.concatenate([tpos, npos], axis=1),
            jnp.concatenate([inten, nint], axis=1))


def _mod_draws(key, n: int, spec: Spec):
    """Which of ``n`` spectra are modified, by how many bins and by what
    precursor mass (see :func:`_modify`)."""
    km, kd, ks = jax.random.split(key, 3)
    is_mod = jax.random.uniform(km, (n,)) < spec.modification_rate
    b_lo, b_hi = spec.modification_bins
    delta = jax.random.randint(kd, (n,), b_lo, b_hi, jnp.int32)
    m_lo, m_hi = spec.modification_mass
    shift = jax.random.uniform(ks, (n,), minval=m_lo, maxval=m_hi)
    return is_mod, delta, shift


def _modify(draws, pos, inten, prec, spec: Spec):
    """Open modifications: a share of the spectra move the upper half of
    the m/z axis up by a few bins (peaks pushed past the last bin are lost;
    peaks just below the middle gain a copy above it) and gain precursor
    mass."""
    is_mod, delta, shift = draws
    half = spec.num_bins // 2
    mod = is_mod[:, None]
    stay = jnp.where(mod & (pos >= half), -1, pos)
    moved = pos + delta[:, None]
    moved = jnp.where(mod & (moved >= half) & (moved < spec.num_bins),
                      moved, -1)
    return (jnp.concatenate([stay, moved], axis=1),
            jnp.concatenate([inten, inten], axis=1),
            jnp.where(is_mod, prec + shift, prec))


def peaks_to_levels(pos, inten, num_bins: int, num_levels: int):
    """Peak lists -> (N, F) quantized levels: normalise to the spectrum's
    highest present peak, quantise present peaks to 1..m-1 (0 = no peak),
    keep the highest level in each bin. Position -1 marks a lost peak."""
    inten = jnp.where(pos >= 0, inten, 0.0)
    mx = jnp.maximum(inten.max(axis=1, keepdims=True), 1e-6)
    v = jnp.clip(inten / mx, 0.0, 1.0)
    lvl = jnp.where(v > 1e-6, 1 + jnp.minimum(
        (v * (num_levels - 1)).astype(jnp.int32), num_levels - 2), 0)
    bins = jnp.arange(num_bins, dtype=jnp.int32)[None, :]

    def add_peak(p, acc):
        hit = pos[:, p, None] == bins
        return jnp.maximum(acc, jnp.where(hit, lvl[:, p, None], 0))

    return jax.lax.fori_loop(0, pos.shape[1], add_peak,
                             jnp.zeros((pos.shape[0], num_bins), jnp.int32))


def pack_bits(positive):
    """(N, D) bool -> (N, D/32) uint32, dimension 32w+j in bit j of word w."""
    n, d = positive.shape
    bits = positive.astype(jnp.uint32).reshape(n, d // 32, 32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=-1, dtype=jnp.uint32)


def encode_pair(levels, id_hvs, base, num_levels: int):
    """Packed hypervectors of the spectra and of their m/z-reversed decoys.

    Eq. 1, HV = sign(sum_f ID_f * LV[level_f]), exploits how the level
    codebook is built: level l is the base vector with dimensions
    [0, thr[l]) flipped, so with P the present-peak mask and
    G_g = [level >= g + 1],

        acc[:, d] = base[d] * (P @ ID - 2 * G_g(d) @ ID)[:, d]

    where g(d) is the group with thr[g] <= d < thr[g + 1] (no flip term
    for d >= D/2). That is one matmul over all D plus one over D/2 in
    groups, with 0/±1 bfloat16 operands and float32 sums, both exact. A
    decoy reverses the m/z axis, which is the same masks against the ID
    codebook with its rows reversed.
    """
    dim = id_hvs.shape[1]
    thr = level_thresholds(dim, num_levels)
    ids = id_hvs.astype(jnp.bfloat16)
    both = jnp.concatenate([ids, ids[::-1]], axis=1)        # (F, 2D)

    def dot(mask, cols):
        return jax.lax.dot(mask.astype(jnp.bfloat16), cols,
                           preferred_element_type=jnp.float32)

    a = dot(levels > 0, both)                                # (N, 2D)
    flips_t, flips_d = [], []
    for g in range(num_levels - 1):
        lo, hi = int(thr[g]), int(thr[g + 1])
        part = dot(levels >= g + 1, jnp.concatenate(
            [both[:, lo:hi], both[:, dim + lo:dim + hi]], axis=1))
        flips_t.append(part[:, :hi - lo])
        flips_d.append(part[:, hi - lo:])
    rest = jnp.zeros((levels.shape[0], dim - int(thr[-1])), jnp.float32)
    sign = base.astype(jnp.float32)
    out = []
    for flips, acc in ((flips_t, a[:, :dim]), (flips_d, a[:, dim:])):
        acc = sign * (acc - 2.0 * jnp.concatenate(flips + [rest], axis=1))
        out.append(pack_bits(acc > 0))
    return out[0], out[1]


def _padded_templates(key, prec_key, spec: Spec):
    pos, inten, prec = _templates(key, prec_key, spec)
    pad = -spec.templates % CHUNK
    return (jnp.pad(pos, ((0, pad), (0, 0))),
            jnp.pad(inten, ((0, pad), (0, 0))), jnp.pad(prec, (0, pad)))


@functools.partial(jax.jit, static_argnums=0)
def template_table(spec: Spec, key, prec_key=None):
    return _padded_templates(key, key if prec_key is None else prec_key,
                             spec)


@functools.partial(jax.jit, static_argnums=0)
def library_pass(spec: Spec, key, prec_key, tpos, tint, tprec, id_hvs,
                 base):
    """One replicate of every template, encoded: (targets, decoys) packed
    (chunks, CHUNK, W), precursors (chunks, CHUNK), and per chunk one
    seeded row with its levels, for the bank encoder's check. The
    precursors' measurement noise comes from ``prec_key``, the rest from
    ``key``."""
    chunks = tpos.shape[0] // CHUNK

    def chunk(c):
        kc = jax.random.fold_in(key, c)
        k_rep, _, k_row = jax.random.split(kc, 3)
        k_prec = jax.random.split(jax.random.fold_in(prec_key, c), 3)[1]
        lo = c * CHUNK
        tp = jax.lax.dynamic_slice_in_dim(tpos, lo, CHUNK)
        ti = jax.lax.dynamic_slice_in_dim(tint, lo, CHUNK)
        pos, inten = _replicate(k_rep, tp, ti, spec)
        levels = peaks_to_levels(pos, inten, spec.num_bins, spec.num_levels)
        prec = (jax.lax.dynamic_slice_in_dim(tprec, lo, CHUNK)
                + spec.precursor_noise * jax.random.normal(k_prec, (CHUNK,)))
        tgt, dec = encode_pair(levels, id_hvs, base, spec.num_levels)
        valid = jnp.minimum(CHUNK, spec.templates - lo)
        row = jax.random.randint(k_row, (), 0, valid)
        return tgt, dec, prec, lo + row, levels[row]

    return jax.lax.map(chunk, jnp.arange(chunks))


def _queries(spec: Spec, kc, ids, tpos, tint, tprec, draws=None,
             noise=None):
    """Fresh replicates of templates ``ids`` with the configuration's
    modification mix, from chunk key ``kc``: levels (uint8), precursors.
    ``draws`` gives the modifications instead (see :func:`_mod_draws`),
    ``noise`` the precursors' measurement noise."""
    _, k_rep, k_prec, k_mod, _ = jax.random.split(kc, 5)
    pos, inten = _replicate(k_rep, tpos[ids], tint[ids], spec)
    if noise is None:
        noise = spec.precursor_noise * jax.random.normal(k_prec, (CHUNK,))
    prec = tprec[ids] + noise
    if draws is None:
        draws = _mod_draws(k_mod, CHUNK, spec)
    pos, inten, prec = _modify(draws, pos, inten, prec, spec)
    levels = peaks_to_levels(pos, inten, spec.num_bins, spec.num_levels)
    return levels.astype(jnp.uint8), prec


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def query_chunks(spec: Spec, chunks: int, strata: int, key, tpos, tint,
                 tprec):
    """``chunks * CHUNK`` query spectra, each a fresh replicate of a
    template with the configuration's modification mix. Position j of
    every run of ``strata`` queries takes its template from the j-th of
    ``strata`` equal slices of the templates ranked by precursor, and each
    run is shuffled: every full batch then spans the precursor range
    alike, so the work of a batch does not depend on the seed. Returns
    levels (uint8) and precursors."""
    t = spec.templates
    ranked = jnp.argsort(tprec[:t]).astype(jnp.int32)
    runs = CHUNK // strata

    def chunk(c):
        kc = jax.random.fold_in(key, c)
        k_id, _, _, _, k_run = jax.random.split(kc, 5)
        slot = jnp.arange(CHUNK, dtype=jnp.int32) % strata
        pick = slot * t // strata + jax.random.randint(
            k_id, (CHUNK,), 0, t // strata, jnp.int32)
        shuffle = (jnp.argsort(jax.random.uniform(k_run, (runs, strata)),
                               axis=1)
                   + jnp.arange(runs, dtype=jnp.int32)[:, None] * strata)
        ids = ranked[pick[shuffle.reshape(-1)]]
        return _queries(spec, kc, ids, tpos, tint, tprec)

    return jax.lax.map(chunk, jnp.arange(chunks))


GOLDEN = 0x9E3779B9  # 2**32 / golden ratio, odd


@functools.partial(jax.jit, static_argnums=(0, 1))
def query_chunks_golden(spec: Spec, chunks: int, key, tpos, tint, tprec):
    """As :func:`query_chunks`, but query i takes the template of
    precursor rank floor(frac(u + i / phi) * templates), a golden-ratio
    sequence from an offset u drawn from ``key``: any n consecutive
    queries split the range into gaps of at most three lengths, the
    largest under 2 / n of it. Whatever n consecutive requests an open
    loop puts in a batch then span the range alike, so the seed moves
    the offset and the spectra, not the work."""
    t = spec.templates
    ranked = jnp.argsort(tprec[:t]).astype(jnp.int32)
    k_off, k_chunks = jax.random.split(key)
    offset = jax.random.bits(k_off, (), jnp.uint32)

    def chunk(c):
        i = (c * CHUNK + jnp.arange(CHUNK)).astype(jnp.uint32)
        frac = offset + i * jnp.uint32(GOLDEN)  # mod 2**32
        rank = ((frac >> 8).astype(jnp.float32) * (t / 2.0 ** 24)).astype(
            jnp.int32)
        ids = ranked[jnp.minimum(rank, t - 1)]
        return _queries(spec, jax.random.fold_in(k_chunks, c), ids, tpos,
                        tint, tprec)

    return jax.lax.map(chunk, jnp.arange(chunks))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def query_chunks_batch_set(spec: Spec, chunks: int, batch: int,
                           set_batches: int, key, set_key, tpos, tint,
                           tprec):
    """As :func:`query_chunks`, but every query is a member of one set of
    ``set_batches`` batches of ``batch``, drawn from ``set_key``: each
    member a precursor rank drawn uniformly (random precursors, as an
    instrument sends them), its precursor's measurement noise and its
    modification. Every cycle of ``set_batches * batch`` queries sends each
    batch of the set once, in an order of the cycle's own drawn from
    ``set_key``, its members shuffled by ``key``. With the library's
    precursors drawn from the set's seed too (``build_library``'s
    ``precursor_seed``), a backlog that dispatches full batches serves the
    same batches in the same order for every seed: the seed moves the
    spectra, not the work."""
    t = spec.templates
    per = batch * set_batches
    cycles = -(-chunks * CHUNK // per)
    ranked = jnp.argsort(tprec[:t]).astype(jnp.int32)
    k_rank, k_mod = jax.random.split(set_key)
    rank = jax.random.randint(k_rank, (per,), 0, t, jnp.int32)
    mods = _mod_draws(k_mod, per, spec)
    noise = spec.precursor_noise * jax.random.normal(
        jax.random.fold_in(set_key, 2), (per,))
    k_order = jax.random.fold_in(set_key, 1)
    order = jax.vmap(lambda c: jnp.argsort(jax.random.uniform(
        jax.random.fold_in(k_order, c), (set_batches,))))(jnp.arange(cycles))
    k_member, k_chunks = jax.random.split(key)
    shuffle = jnp.argsort(
        jax.random.uniform(k_member, (cycles, set_batches, batch)), axis=2)
    member = (order[:, :, None] * batch + shuffle).reshape(-1)

    def chunk(c):
        m = jax.lax.dynamic_slice_in_dim(member, c * CHUNK, CHUNK)
        return _queries(spec, jax.random.fold_in(k_chunks, c),
                        ranked[rank[m]], tpos, tint, tprec,
                        tuple(d[m] for d in mods), noise[m])

    return jax.lax.map(chunk, jnp.arange(chunks))


@dataclasses.dataclass
class Library:
    """The bank as the deployment loads it, kept on the host."""

    targets: np.ndarray      # (N, W) uint32
    decoys: np.ndarray       # (N, W) uint32, decoy i reverses target i
    precursor: np.ndarray    # (N,) float32, decoys share their target's
    id_hvs: np.ndarray       # (F, D) int8
    level_hvs: np.ndarray    # (m, D) int8
    checked_rows: np.ndarray    # (S,) library rows kept for the encoder check
    checked_levels: np.ndarray  # (S, F) their levels

    @property
    def num_rows(self) -> int:
        return int(self.targets.shape[0])


def build_library(spec: Spec, seed: int, log=print,
                  precursor_seed: int | None = None) -> tuple[Library, tuple]:
    """Generate and encode the library pass by pass on the device, staging
    each pass on the host while the next one runs. Returns the library and
    the device template table (for the query pool). ``precursor_seed``, if
    given, draws every precursor (the templates' and their measurement
    noise) in place of ``seed``, so that the library's precursor layout is
    the same for every seed."""
    id_hvs, level_hvs, base = codebooks(seed_key(seed, 1), spec.dim,
                                        spec.num_bins, spec.num_levels)
    p_seed = seed if precursor_seed is None else precursor_seed
    table = template_table(spec, seed_key(seed, 2), seed_key(p_seed, 2))
    n, t = spec.library_rows, spec.templates
    targets = np.empty((n, spec.words), np.uint32)
    decoys = np.empty((n, spec.words), np.uint32)
    prec = np.empty((n,), np.float32)
    rows, levels = [], []

    def stage(p, out):
        tgt, dec, pr, row, lv = out
        lo = p * t
        targets[lo:lo + t] = np.asarray(tgt).reshape(-1, spec.words)[:t]
        decoys[lo:lo + t] = np.asarray(dec).reshape(-1, spec.words)[:t]
        prec[lo:lo + t] = np.asarray(pr).reshape(-1)[:t]
        rows.append(np.asarray(row) + lo)
        levels.append(np.asarray(lv))

    lib_key, prec_key = seed_key(seed, 3), seed_key(p_seed, 3)
    pending = None
    for p in range(spec.passes):
        out = library_pass(spec, jax.random.fold_in(lib_key, p),
                           jax.random.fold_in(prec_key, p), *table,
                           id_hvs, base)
        if pending is not None:
            stage(*pending)
        pending = (p, out)
    stage(*pending)
    lib = Library(targets=targets, decoys=decoys, precursor=prec,
                  id_hvs=np.asarray(id_hvs), level_hvs=np.asarray(level_hvs),
                  checked_rows=np.concatenate(rows),
                  checked_levels=np.concatenate(levels).astype(np.uint8))
    return lib, table


@dataclasses.dataclass
class QueryPool:
    levels: np.ndarray      # (Q, F) uint8
    precursor: np.ndarray   # (Q,) float32

    def __len__(self) -> int:
        return int(self.levels.shape[0])


def query_pool(spec: Spec, seed: int, tag: int, count: int, table,
               strata: int = 1, order: str = "stratified",
               set_batches: int = 0, set_seed: int = 0) -> QueryPool:
    """``count`` distinct query spectra of stream ``tag``, in precursor
    order ``stratified`` (by runs of ``strata``, see :func:`query_chunks`),
    ``golden`` (see :func:`query_chunks_golden`) or ``random`` (a set of
    ``set_batches`` random batches of ``strata`` drawn from ``set_seed``,
    see :func:`query_chunks_batch_set`)."""
    chunks = max(1, -(-count // CHUNK))
    key = seed_key(seed, tag)
    if order == "golden":
        lv, pr = query_chunks_golden(spec, chunks, key, *table)
    elif order == "random":
        if set_batches < 1:
            raise ValueError("a random order needs set_batches >= 1")
        lv, pr = query_chunks_batch_set(spec, chunks, strata, set_batches,
                                        key, seed_key(set_seed, SET_TAG),
                                        *table)
    elif order == "stratified":
        if CHUNK % strata or spec.templates < strata:
            raise ValueError(f"{strata} strata do not divide {CHUNK}-row "
                             f"chunks of {spec.templates} templates")
        lv, pr = query_chunks(spec, chunks, strata, key, *table)
    else:
        raise ValueError(f"unknown precursor order {order!r}")
    return QueryPool(levels=np.asarray(lv).reshape(-1, spec.num_bins)[:count],
                     precursor=np.asarray(pr).reshape(-1)[:count])
