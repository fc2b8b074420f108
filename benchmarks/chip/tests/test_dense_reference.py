"""The dense family's reference reads, bit for bit, what it read before it
moved into ``models/dense.py``: the losses and norms below are the
reference's own on the CPU, taken from the code as it stood when the
dense equations lived in ``reference.py``. A change to the family's
arithmetic, or to the shared loop, head or Adam, shows here first."""
import pytest

import harness
import program
import reference
import tiny
import traffic_gen

SEED = 2**33 + 7

BEFORE = {
    "gelu_tanh": {
        "losses": [5.933677911758423, 5.951383113861084, 5.8853278160095215],
        "grad_norms": {
            "embed": 1.31627973633421,
            "final_norm": 0.15318837533005794,
            "layer0/attn/wk": 0.5174797108492221,
            "layer0/attn/wo": 0.943178874373673,
            "layer0/attn/wq": 0.5234117643947368,
            "layer0/attn/wv": 1.0351180735332035,
            "layer0/mlp/w_in": 0.8133376278789936,
            "layer0/mlp/w_out": 1.1070358437797518,
            "layer0/norm1": 0.14174903556539373,
            "layer0/norm2": 0.0942074819251056,
            "layer1/attn/wk": 0.17566282733790917,
            "layer1/attn/wo": 0.7011947840611406,
            "layer1/attn/wq": 0.1802067406993749,
            "layer1/attn/wv": 0.6524075688193798,
            "layer1/mlp/w_in": 0.48363817223550015,
            "layer1/mlp/w_out": 0.6870410861399353,
            "layer1/norm1": 0.0762717924381187,
            "layer1/norm2": 0.05717274418464942,
            "unembed": 0.9838177753838019,
        },
        "change_norms": {
            "embed": 0.15821818291858036,
            "final_norm": 0.018809782898726837,
            "layer0/attn/wk": 0.09000768942402783,
            "layer0/attn/wo": 0.1267980361857102,
            "layer0/attn/wq": 0.12834186982532453,
            "layer0/attn/wv": 0.08927539718999453,
            "layer0/mlp/w_in": 0.18012821014040145,
            "layer0/mlp/w_out": 0.18150939097358681,
            "layer0/norm1": 0.01743728789312473,
            "layer0/norm2": 0.016956914198650922,
            "layer1/attn/wk": 0.09014706619400578,
            "layer1/attn/wo": 0.12832142642160413,
            "layer1/attn/wq": 0.12804409182341786,
            "layer1/attn/wv": 0.08725963428531061,
            "layer1/mlp/w_in": 0.1794080427020763,
            "layer1/mlp/w_out": 0.1823214415802023,
            "layer1/norm1": 0.015960250250243422,
            "layer1/norm2": 0.016542527827384787,
            "unembed": 0.28540746518545396,
        },
    },
    "swiglu": {
        "losses": [6.037539720535278, 6.112297058105469, 6.062993288040161],
        "grad_norms": {
            "embed": 1.8981549087996725,
            "final_norm": 0.16004881511839242,
            "layer0/attn/wk": 0.8943365293235512,
            "layer0/attn/wo": 1.2730610765037835,
            "layer0/attn/wq": 0.8318582632557917,
            "layer0/attn/wv": 1.3895086887028936,
            "layer0/mlp/w_down": 1.2318432593744217,
            "layer0/mlp/w_gate": 0.8173635610412106,
            "layer0/mlp/w_up": 0.8281396828555364,
            "layer0/norm1": 0.19388437764074465,
            "layer0/norm2": 0.12463359104181862,
            "layer1/attn/wk": 0.24762387891454973,
            "layer1/attn/wo": 0.7455993931890647,
            "layer1/attn/wq": 0.28010210921464773,
            "layer1/attn/wv": 0.6870663749247565,
            "layer1/mlp/w_down": 0.6783060746218567,
            "layer1/mlp/w_gate": 0.5188558102898053,
            "layer1/mlp/w_up": 0.5093209969381374,
            "layer1/norm1": 0.07337559312535219,
            "layer1/norm2": 0.07733716695879361,
            "unembed": 0.9955317389038009,
        },
        "change_norms": {
            "embed": 0.15593694080948187,
            "final_norm": 0.019867866981002777,
            "layer0/attn/wk": 0.08999566006461795,
            "layer0/attn/wo": 0.12577012617075534,
            "layer0/attn/wq": 0.12613047579021172,
            "layer0/attn/wv": 0.09055693697214977,
            "layer0/mlp/w_down": 0.17912235742484472,
            "layer0/mlp/w_gate": 0.17797422469294816,
            "layer0/mlp/w_up": 0.17820548405940062,
            "layer0/norm1": 0.016225182424642593,
            "layer0/norm2": 0.0152544110693292,
            "layer1/attn/wk": 0.08919549390058205,
            "layer1/attn/wo": 0.1248989665489758,
            "layer1/attn/wq": 0.12470787155597143,
            "layer1/attn/wv": 0.08942821022453797,
            "layer1/mlp/w_down": 0.17757045606776617,
            "layer1/mlp/w_gate": 0.17753415830110494,
            "layer1/mlp/w_up": 0.17863632687519865,
            "layer1/norm1": 0.015027401323111512,
            "layer1/norm2": 0.01605594070328032,
            "unembed": 0.2698118159356369,
        },
    },
}


@pytest.mark.parametrize("act", sorted(BEFORE))
def test_dense_reference_reads_as_before(act):
    cfg = dict(tiny.CONFIG, mlp=act)
    model = harness.Registry().model_of(cfg)
    batches = traffic_gen.batches(tiny.TRAFFIC, cfg["vocab_size"], SEED, 3)
    got = reference.run(model, model.Arch.from_config(cfg),
                        program.seed_key(SEED), tiny.TRAFFIC["lr"],
                        tiny.TRAFFIC["micro_batches"], batches)
    assert got == BEFORE[act]
