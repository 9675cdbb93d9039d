"""Metrics-docs conformance: every metric family any `*Instruments`
class can register must have a row in docs/observability.md.

The test instantiates EVERY instruments bundle on a fresh registry and
touches each lazily-created labeled child, so the family list below is
the real registered surface, not a hand-maintained copy.  A new metric
added without a docs row fails here — the docs table is load-bearing.
"""
import os

import pytest

from deeplearning4j_tpu.monitor import instrument as I
from deeplearning4j_tpu.monitor.forecast import ArrivalRateForecaster
from deeplearning4j_tpu.monitor.registry import MetricsRegistry

DOCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs", "observability.md")

# Families registered through the process-global registry by code that
# cannot run against an injected one (utils.chaos counts via the global
# singleton); kept literal so a rename still trips the docs check.
GLOBAL_REGISTRY_FAMILIES = {"chaos_faults_injected_total"}


def _register_everything(reg: MetricsRegistry):
    """Instantiate every instruments bundle and touch every lazy child."""
    I.TrainingInstruments("mlp", reg)
    I.PipelineInstruments(reg)
    I.ParallelInstruments(reg)
    I.ResilienceInstruments(reg)
    I.AotCacheInstruments(reg)
    I.CommsInstruments(reg)
    I.GangInstruments(reg).reformations("crash")
    fleet = I.FleetInstruments(reg)
    fleet.requests("m")
    fleet.sheds("m", 0)
    fleet.breaches("m")
    fleet.respawns("poisoned")
    fleet.breaker_state("m")
    fed = I.FederationInstruments(reg)
    fed.evictions("crash")
    fed.record_replacement(True, 1.0)
    I.QuantInstruments(reg).models("int8")
    I.OpsInstruments(reg).dispatch("matmul", "pallas")
    dec = I.DecodeInstruments(reg)
    dec.tokens("m")
    dec.inter_token("m")
    dec.kv_blocks("m")
    dec.kv_bytes("m", "int8")
    dec.sequences_active("m")
    dec.restarts("m")
    arb = I.ArbiterInstruments(reg)
    arb.handoffs("to_serving", "committed")
    arb.slices("training")
    # forecaster gauge is minted on the first post-baseline tick
    fc = ArrivalRateForecaster(registry_=reg)
    reg.counter("fleet_requests_total", labels={"model": "m"}).inc(10)
    fc.tick(now=100.0)
    reg.counter("fleet_requests_total", labels={"model": "m"}).inc(10)
    fc.tick(now=101.0)


def test_every_registered_family_is_documented():
    reg = MetricsRegistry()
    _register_everything(reg)
    families = set(reg.families()) | GLOBAL_REGISTRY_FAMILIES
    assert "fleet_arrival_forecast" in families  # forecaster ticked above
    with open(DOCS) as f:
        doc = f.read()
    missing = sorted(n for n in families if n not in doc)
    assert not missing, (
        f"{len(missing)} metric families lack a docs/observability.md "
        f"row: {missing}")


def test_documented_series_exist():
    """The reverse direction: every `things_total`-shaped name the docs
    table mentions must still be a registrable family — rows must not
    outlive a metric rename."""
    import re
    reg = MetricsRegistry()
    _register_everything(reg)
    families = set(reg.families()) | GLOBAL_REGISTRY_FAMILIES
    with open(DOCS) as f:
        doc = f.read()
    # backticked bare family names in table rows (strip label stubs);
    # wildcard rows like `serving_*{server=}` document a namespace that
    # lives outside the instruments bundles — skip those
    stale = []
    for m in re.finditer(r"`([a-z0-9_]+)(?:\{[^`]*\})?`", doc):
        name = m.group(1)
        prefix = name.split("_")[0]
        if prefix in ("training", "pipeline", "parallel", "resilience",
                      "aot", "comms", "gang", "fleet", "fed", "quant",
                      "ops", "chaos", "decode", "arbiter") \
                and name not in families:
            stale.append(name)
    assert not stale, f"docs rows reference unknown families: {sorted(set(stale))}"


def test_every_decoder_counter_is_documented():
    """Each counter a `DecoderModel` train step keeps in `state_`, of every
    kind of layer, and each of its layers' device scopes, has its name in
    the docs' tables."""
    from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel
    with open(DOCS) as f:
        doc = f.read()
    names = set()
    for preset in ("tiny", "tiny_hybrid", "tiny_diffusion", "tiny_sparse",
                   "tiny_linear", "tiny_mamba"):
        names |= set(DecoderModel(getattr(DecoderConfig, preset)()).state_)
    names |= {"linear_attention", "delta_rule", "gqa_attention",
              "sparse_index", "index_loss", "mla_attention", "mamba_mixer",
              "ssd_scan"}
    missing = sorted(n for n in names if f"`{n}`" not in doc)
    assert not missing, missing


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
