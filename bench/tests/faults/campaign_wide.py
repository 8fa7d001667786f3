"""Faults of a ``campaign_wide`` cell's timed path: its timed path is the
campaign loop's, so they are the campaign cells' (``faults/campaign.py``),
at the same small size."""
from fault_run import faults

_campaign = faults("campaign")
SMALL, FAULTS, plant = _campaign.SMALL, _campaign.FAULTS, _campaign.plant
