"""Structural-health-monitoring use-case (paper §7.5): damage diagnostics
with an ensemble of VM nodes.

A plate carries virtual sensor nodes; a pseudo-defect (paper: neodymium
magnet) sits at an unknown position.  Each node runs the measuring job +
a fixed-point ANN (trained offline here in numpy, parameters embedded in
the code frame) to estimate the defect distance; the master fuses node
estimates.  A corrupted node is caught by ensemble majority voting
(paper resilience 4).

    PYTHONPATH=src python examples/shm_ann.py
"""

import numpy as np

from repro.config import VMConfig
from repro.core.vm import REXAVM
from repro.launch.cache import enable_compile_cache


def simulate_echo(dist: float, rng, n=48):
    t = np.arange(n)
    center = 8 + dist * 30
    echo = np.sin(t / 1.3) * np.exp(-((t - center) ** 2) / 18.0) * 800
    return (echo + rng.normal(0, 25, n)).astype(np.int32)


def train_readout(rng):
    """Offline float training of a 2-feature -> distance readout, then
    fixed-point conversion with scale vectors (paper §4)."""
    feats, targets = [], []
    for _ in range(400):
        d = rng.uniform(0, 1)
        echo = simulate_echo(d, rng)
        env = np.abs(echo)
        for _ in range(3):
            env = env * 0.6 + np.roll(env, 1) * 0.4
        peak = env.argmax()
        feats.append([peak, env[peak] // 8])
        targets.append(d * 1000)
    X = np.array(feats, float)
    y = np.array(targets, float)
    Xb = np.concatenate([X, np.ones((len(X), 1))], axis=1)
    w, *_ = np.linalg.lstsq(Xb, y, rcond=None)
    return w  # [w_peak, w_amp, bias]


def node_program(w):
    """Embed the fixed-point readout into a measuring-job code frame."""
    wp, wa, b = (int(round(v * 16)) for v in w)  # Q4 fixed point
    return f"""
    10 1 1 100 adc
    1000 1 sampled await
    0< if ." timeout" cr end endif
    samples 0 48 400 hull
    samples vecmax
    dup {wp} 16 */
    swap samples get 8 / {wa} 16 */
    + {b} 16 / +
    out
    """


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    w = train_readout(rng)
    true_defect = 0.62
    print(f"true defect position: {true_defect:.2f}")
    print("node  est(x1000)  |err|")
    estimates = []
    for node in range(5):
        cfg = VMConfig(cs_size=8192, steps_per_slice=2048)
        vm = REXAVM(cfg, backend="oracle")
        vm.dios_add("samples", np.zeros(48, np.int32))
        vm.dios_add("sampled", np.array([0], np.int32))
        echo = simulate_echo(true_defect, np.random.default_rng(node))

        def adc(trig, depth, gain, freq, echo=echo, vm=vm):
            vm.dios_write("samples", echo)
            vm.dios_write("sampled", [1])

        vm.fios_add("adc", adc, args=4, ret=0)
        res = vm.eval(node_program(w), max_slices=500)
        assert res.status == "done", res.status
        est = vm.out_stream[0]
        # node 3 suffers a bit-flip on its report (paper §2.6 data corruption)
        if node == 3:
            est ^= 0x400
        estimates.append(est)
        print(f"n{node}    {est:6d}      {abs(est - true_defect*1000):5.0f}")

    # master-side majority/median fusion rejects the corrupted node
    med = int(np.median(estimates))
    kept = [e for e in estimates if abs(e - med) < 200]
    fused = np.mean(kept) / 1000
    print(f"fused estimate {fused:.2f} (rejected {len(estimates)-len(kept)} "
          f"corrupted node(s)); error {abs(fused-true_defect):.3f}")


if __name__ == "__main__":
    main()
