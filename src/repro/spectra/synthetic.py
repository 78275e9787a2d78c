"""Synthetic tandem-MS spectra with ground-truth identities.

The real datasets (PXD001468, PXD000561, iPRG2012, HEK293) are not available
offline, so we generate peptide-like spectra that preserve the statistics the
HD pipeline actually consumes:

  * each "peptide" is a sparse template of fragment peaks over an m/z range
    (drawn once per identity),
  * each observed spectrum is a template plus peak-intensity jitter, peak
    dropout, small m/z shifts, and chemical-noise peaks,
  * spectra carry a precursor mass used for bucketing (clustering) and
    candidate windowing (DB search),
  * open-modification variants shift a suffix of peaks by a delta mass — the
    case HyperOMS/ANN-SoLo target and the reason FDR filtering matters.

Ground truth (template id per spectrum) enables the paper's quality metrics:
clustered-spectra ratio at fixed incorrect-clustering ratio (Fig. 9) and
identified peptides at fixed FDR (Fig. 10).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SyntheticMSConfig:
    num_identities: int = 64          # distinct peptides
    spectra_per_identity: int = 16    # replicates (cluster sizes)
    num_bins: int = 1024              # m/z bins after preprocessing
    peaks_per_peptide: int = 48       # fragment peaks per template
    intensity_jitter: float = 0.25    # multiplicative log-normal-ish jitter
    dropout: float = 0.15             # per-peak missing probability
    # m/z calibration error in bins. 0 by default: preprocessing bins at the
    # instrument calibration width, so residual shift is sub-bin (ID-level
    # encoding is not shift-tolerant by construction — same as HyperSpec).
    mz_shift_bins: int = 0
    noise_peaks: int = 12             # chemical noise peaks per spectrum
    modification_rate: float = 0.0    # fraction of spectra with a mass shift
    # precursor-mass shift range for modified spectra (opt-in; (0, 0) keeps
    # the precursor at the unmodified identity's mass). A modification makes
    # the observed peptide *heavier*, which is what open-modification search
    # widens the window for — set e.g. (60.0, 90.0) to exercise OMS.
    modification_mass_range: tuple[float, float] = (0.0, 0.0)
    precursor_range: tuple[float, float] = (400.0, 1600.0)
    seed: int = 0            # instance noise (jitter/dropout/noise peaks)
    template_seed: int = 42  # peptide templates — fixed across query/ref sets


@dataclasses.dataclass
class MSDataset:
    spectra: jax.Array        # (N, num_bins) float32 in [0, 1]
    identity: jax.Array       # (N,) int32 ground-truth template id
    precursor: jax.Array      # (N,) float32 precursor mass
    is_modified: jax.Array    # (N,) bool
    templates: jax.Array      # (num_identities, num_bins)

    @property
    def num_spectra(self) -> int:
        return self.spectra.shape[0]


@functools.partial(jax.jit, static_argnums=2)
def _peaks_to_bins(pos: jax.Array, inten: jax.Array, num_bins: int
                   ) -> jax.Array:
    """(N, P) peak bins and intensities -> (N, num_bins): the largest
    intensity landing in each bin, 0 where none does. A compare-and-reduce
    rather than a scatter-max: it fuses into one reduction, where XLA takes
    minutes to compile a TPU scatter of millions of peaks. Jitted so that
    eager callers get the fusion too, never the (N, P, num_bins) mask."""
    hit = pos[:, :, None] == jnp.arange(num_bins, dtype=pos.dtype)
    return jnp.max(jnp.where(hit, inten[:, :, None], 0.0), axis=1)


def _make_templates(key, cfg: SyntheticMSConfig) -> jax.Array:
    kp, ki = jax.random.split(key)
    # peak positions: distinct bins per identity
    pos = jax.random.uniform(kp, (cfg.num_identities, cfg.peaks_per_peptide))
    pos = (pos * cfg.num_bins).astype(jnp.int32) % cfg.num_bins
    inten = jax.random.uniform(
        ki, (cfg.num_identities, cfg.peaks_per_peptide), minval=0.2, maxval=1.0
    )
    return _peaks_to_bins(pos, inten, cfg.num_bins)


def generate_dataset(cfg: SyntheticMSConfig) -> MSDataset:
    key = jax.random.PRNGKey(cfg.seed)
    _, k_j, k_d, k_s, k_n, k_p, k_m, k_mod = jax.random.split(key, 8)
    k_t = jax.random.PRNGKey(cfg.template_seed)
    templates = _make_templates(k_t, cfg)
    n = cfg.num_identities * cfg.spectra_per_identity
    identity = jnp.repeat(jnp.arange(cfg.num_identities, dtype=jnp.int32),
                          cfg.spectra_per_identity)
    base = templates[identity]  # (N, bins)

    # intensity jitter (multiplicative)
    jit = 1.0 + cfg.intensity_jitter * jax.random.normal(k_j, base.shape)
    spec = base * jnp.clip(jit, 0.1, 2.0)

    # peak dropout
    keep = jax.random.uniform(k_d, base.shape) > cfg.dropout
    spec = jnp.where(keep, spec, 0.0)

    # m/z calibration shift: roll each spectrum by a small random offset
    shifts = jax.random.randint(
        k_s, (n,), -cfg.mz_shift_bins, cfg.mz_shift_bins + 1
    )
    idx = (jnp.arange(cfg.num_bins)[None, :] - shifts[:, None]) % cfg.num_bins
    spec = jnp.take_along_axis(spec, idx, axis=1)

    # chemical noise peaks
    npos = jax.random.randint(k_n, (n, cfg.noise_peaks), 0, cfg.num_bins)
    nint = jax.random.uniform(k_n, (n, cfg.noise_peaks), minval=0.05, maxval=0.35)
    spec = jnp.maximum(spec, _peaks_to_bins(npos, nint, cfg.num_bins))

    # open modification: shift the top half of the m/z axis by a delta
    is_mod = jax.random.uniform(k_mod, (n,)) < cfg.modification_rate
    delta = jax.random.randint(k_m, (n,), 8, 48)
    half = cfg.num_bins // 2
    midx = (jnp.arange(cfg.num_bins)[None, :] - delta[:, None]) % cfg.num_bins
    shifted = jnp.take_along_axis(spec, midx, axis=1)
    spec_mod = jnp.concatenate([spec[:, :half], shifted[:, half:]], axis=1)
    spec = jnp.where(is_mod[:, None], spec_mod, spec)

    # precursor mass: a *deterministic* function of identity (golden-ratio
    # hash over the mass range) so query sets generated with different seeds
    # still share precursors with their reference identities, plus small
    # measurement noise
    lo, hi = cfg.precursor_range
    phi = 0.6180339887498949
    ids = jnp.arange(cfg.num_identities, dtype=jnp.float32)
    prec_id = (lo + (hi - lo) * ((ids * phi) % 1.0)).astype(jnp.float32)
    precursor = prec_id[identity] + 0.02 * jax.random.normal(k_p, (n,))

    # opt-in: modified spectra get a heavier precursor (the OMS scenario);
    # keyed by fold_in so enabling it leaves every other random stream —
    # and therefore all default-config outputs — bit-identical
    m_lo, m_hi = cfg.modification_mass_range
    if m_hi > m_lo:
        shift = jax.random.uniform(jax.random.fold_in(key, 97), (n,),
                                   minval=m_lo, maxval=m_hi)
        precursor = jnp.where(is_mod, precursor + shift, precursor)
    elif m_lo == m_hi and m_hi > 0.0:
        precursor = jnp.where(is_mod, precursor + m_hi, precursor)

    # normalize to [0, 1] per spectrum
    mx = jnp.maximum(spec.max(axis=1, keepdims=True), 1e-6)
    spec = spec / mx
    return MSDataset(
        spectra=spec, identity=identity, precursor=precursor,
        is_modified=is_mod, templates=templates,
    )


def generate_query_set(
    dataset: MSDataset, cfg: SyntheticMSConfig, num_queries: int, seed: int = 1,
    modification_rate: float = 0.3,
) -> MSDataset:
    """Fresh replicates of a subset of identities, to use as DB-search
    queries against the dataset's templates (the reference library)."""
    qcfg = dataclasses.replace(
        cfg,
        spectra_per_identity=max(1, num_queries // cfg.num_identities),
        seed=seed,
        modification_rate=modification_rate,
    )
    return generate_dataset(qcfg)
