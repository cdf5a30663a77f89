"""Thermal Monte-Carlo campaign engine (DESIGN.md §5, §8, §9).

Packs (corner x temperature x voltage x pulse x sample) reliability grids
into the Pallas thermal LLG kernel's ``(8, cells)`` SoA layout —
temperature rides the lanes as a per-lane Brown sigma and process corners
as per-lane device-parameter rows (``CampaignGrid.variation``), so a
whole campaign is one launch with one compile — shards cell tiles across
devices, and reduces first-crossing steps into WER / latency surfaces
with on-disk result caching.

  grid    — CampaignGrid axes + SoA packing (fused-CT plane, shape buckets)
  engine  — run_campaign / run_ensemble + surface reductions + early exit
            + streaming on-device reduction / multi-process
            mesh launch partitioning (DESIGN.md §14)
  cache   — content-addressed npz result cache + lockless work claims
"""
from repro.campaign.cache import campaign_key  # noqa: F401
from repro.campaign.engine import (  # noqa: F401
    EARLY_EXIT_CHUNK,
    CampaignResult,
    EnsembleResult,
    brown_sigma,
    run_campaign,
    run_ensemble,
)
from repro.campaign.grid import (  # noqa: F401
    CampaignGrid,
    bucket_cells,
    log_horizon_bucket,
    log_pulses,
    pack_campaign,
    pack_plane,
    pack_soa,
    pack_variation,
)
