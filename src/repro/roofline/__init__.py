"""Roofline terms from a compiled program's cost analysis and HLO text."""

from repro.roofline.analysis import (HW, collective_bytes_from_hlo,
                                     roofline_terms)
