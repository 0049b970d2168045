"""Sensing substrate: synthetic radar data, ADC simulation, fragment
sampling, baseline detectors (CRUW stand-in; DESIGN.md §1), the chunked
gate step (:mod:`repro.sensing.stream`), and the fleet step builder
(:mod:`repro.sensing.fleet`). The runtime that drives the step is
:class:`repro.launch.serve.FleetService`."""

from repro.sensing import (adc, baselines, fleet, fragments,  # noqa: F401
                           stream, synthetic)
