"""Roofline analysis over compiled dry-run artifacts."""

from .analysis import analyze_compiled, roofline_terms
from .constants import CHIPS, ChipPeaks, chip_peaks
from .hlo import parse_collectives

__all__ = ["CHIPS", "ChipPeaks", "analyze_compiled", "chip_peaks",
           "parse_collectives", "roofline_terms"]
