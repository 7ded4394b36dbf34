"""The v5e compiler's verdict without a chip.

The installed libtpu can compile for the real target with no TPU attached:
``jax.experimental.topologies.get_topology_desc("tpu", "v5e:2x2")`` describes
four ``TPU v5 lite`` devices, and ``jit(f).lower(<ShapeDtypeStructs sharded
on them>).compile()`` runs the real XLA:TPU and Mosaic compilers. The Pallas
kernels only ever *execute* here under ``interpret=True``, which accepts
programs Mosaic refuses (an ``[N, 1]`` f32 column costs 512 bytes per row of
VMEM on the chip and nothing in the interpreter) — so every kernel
``TrainConfig`` can reach is compiled for the v5e at the shapes ``Trainer``
produces, with ``interpret=False``. Execution parity on the chip is
``chip_smoke.py``'s half.

The standalone kernels take seconds and stay in tier-1; the fused-step
compiles (about a minute each) are marked ``slow``.

On a machine that HOLDS a chip the module skips itself: building the
topology loads libtpu, which takes the machine's ``/tmp/libtpu_lockfile``
even under ``JAX_PLATFORMS=cpu`` — measured on the v5e host (PR 21): while
this process lived, a second process could not open the TPU ("Internal
error when accessing libtpu multi-process lockfile"). There the check
belongs to ``chip_smoke.py``, which compiles and runs the same kernels on
the chip itself.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mercury_tpu.ops import mercury_kernels


def _chip_attached() -> bool:
    """A TPU is attached to this machine — asked of the device nodes and
    the PCI bus (as jax's own detection does), never of libtpu."""
    if glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"):
        return True
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        with open(vendor) as f:
            if f.read().strip() == "0x1ae0":  # Google
                return True
    return False


@pytest.fixture(scope="module")
def v5e_devices():
    if _chip_attached():
        pytest.skip("a TPU is attached: a compile-only libtpu client would "
                    "take its process lock; chip_smoke.py checks the "
                    "kernels on the chip")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu, or one that cannot describe v5e
        pytest.skip(f"cannot build a v5e:2x2 topology here: "
                    f"{type(exc).__name__}: {exc}")
    assert [d.device_kind for d in topo.devices] == ["TPU v5 lite"] * 4
    return topo.devices


@pytest.fixture
def mosaic(monkeypatch):
    """Make the kernel wrappers emit real Mosaic calls (the on-chip
    value of ``interpret``) although this process runs on the CPU."""
    monkeypatch.setattr(mercury_kernels, "on_tpu", lambda: True)
    assert mercury_kernels._interpret() is False


def _compiled(fn, devices, *shapes):
    """Compile ``fn`` for one v5e device; returns the executable."""
    sh = NamedSharding(Mesh(np.array(devices[:1]), ("data",)), P())
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _compile(fn, devices, *shapes):
    """Compile ``fn`` for one v5e device; returns the executable's text."""
    return _compiled(fn, devices, *shapes).as_text()


#: ``conv2``'s raw output in each stage of the cell's scoring forward.
CONV2_MAPS = [(2560, 32, 32, 64), (2560, 16, 16, 128), (2560, 8, 8, 256),
              (2560, 4, 4, 512)]


class TestKernelsCompileForV5e:
    @pytest.mark.parametrize("n,c,dtype", [
        (320, 10, jnp.float32), (32, 10, jnp.float32),
        (320, 100, jnp.float32), (320, 10, jnp.bfloat16),
    ])
    def test_per_sample_nll_fwd_and_vjp(self, v5e_devices, mosaic,
                                        n, c, dtype):
        def fwd_bwd(logits, labels):
            return jax.value_and_grad(
                lambda z: jnp.sum(
                    mercury_kernels.per_sample_nll_pallas(z, labels))
            )(logits)

        text = _compile(fwd_bwd, v5e_devices,
                        ((n, c), dtype), ((n,), jnp.int32))
        assert text.count("tpu_custom_call") >= 2  # forward + backward

    @pytest.mark.parametrize("n,b", [
        (320, 32),      # the reference pool (batch 32 x 10)
        (2560, 256),    # batch 256 x 10
        (5000, 32),     # scoretable: the synthetic shard at W=1
        (12500, 32),    # scoretable: CIFAR-10 at W=4
        (50000, 32),    # scoretable: CIFAR-10 at W=1
    ])
    def test_score_and_draw(self, v5e_devices, mosaic, n, b):
        """An ``[N, 1]`` column layout is refused from N=12,500 up (18.5 MB
        of scoped VMEM against the 16 MB limit); lane-dense it fits."""
        def draw(key_data, losses, ema):
            return mercury_kernels.score_and_draw_pallas(
                jax.random.wrap_key_data(key_data), losses, ema, b)

        text = _compile(draw, v5e_devices, ((2,), jnp.uint32),
                        ((n,), jnp.float32), ((), jnp.float32))
        assert "tpu_custom_call" in text


    @pytest.mark.parametrize("shape,dtype", [
        *[(shape, jnp.bfloat16) for shape in CONV2_MAPS],
        # what else reaches the kernel: every non-differentiated Bottleneck
        # forward, whatever the pool, the image and the dtype
        ((320, 32, 32, 64), jnp.bfloat16),    # 320 lanes: one chunk, not 128s
        ((80, 32, 32, 64), jnp.bfloat16),     # the pool of 320 over 4 chips
        ((320, 4, 4, 512), jnp.bfloat16),     # 5,120 rows: last block masked
        ((32, 4, 4, 512), jnp.bfloat16),      # 512 rows: less than a chunk
        ((2560, 32, 32, 64), jnp.float32),    # f32 maps, both views
        ((2560, 16, 16, 128), jnp.float32),
        ((2560, 3, 3, 64), jnp.bfloat16),     # K < 128, nothing to stack
        ((256, 56, 56, 64), jnp.bfloat16),    # ImageNet's first and last
        ((256, 7, 7, 512), jnp.bfloat16),     # stage: odd rows of positions
        ((2560, 32, 32, 32), jnp.bfloat16),   # four positions stacked
    ], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple)
        else jnp.dtype(v).name)
    def test_input_moments(self, v5e_devices, mosaic, shape, dtype):
        """The closing unit's moments kernel at the benchmark cell's four
        ``conv2`` maps (pool of 2,560, bf16): the batch-in-lanes view with
        two positions stacked (K = 64) and the channels-in-lanes view; and
        at the other pools, image sizes and dtype a ``Bottleneck`` model is
        run at, since no shape falls back to XLA's two passes."""
        k = shape[-1]
        text = _compile(
            lambda y, mean, mul, bias: mercury_kernels.input_moments_pallas(
                y, mean, mul, bias, dtype),
            v5e_devices, (shape, dtype), *[((k,), jnp.float32)] * 3)
        assert text.count("tpu_custom_call") == 1

    @pytest.mark.parametrize("d, v", [(2560, 18992), (2048, 16032)],
                             ids=["st21b-is-8k", "kn2-is-8k"])
    def test_head_nll(self, v5e_devices, mosaic, d, v):
        """The head's kernel over vocabulary blocks at the two token cells'
        shapes, bfloat16 operands: one sequence of 8,192 tokens, a
        vocabulary that is no multiple of 128 (the last block masked), the
        whole contraction's operand blocks, the 1,024 x 1,024 float32 tile
        and the fold's temporaries in the VMEM the call asks for. What leaves is two float32 numbers a token."""
        assert mercury_kernels.head_nll_takes(8192, d)
        compiled = _compiled(
            mercury_kernels.head_nll_pallas, v5e_devices,
            ((8192, d), jnp.bfloat16), ((d, v), jnp.bfloat16),
            ((8192,), jnp.int32))
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        assert "mercury_head_nll" in text
        assert f"f32[8192,{v}]" not in text
        assert compiled.memory_analysis().output_size_in_bytes < 2 ** 17

    def test_synced_unit_checks_vma(self, v5e_devices, mosaic):
        """A ``Bottleneck`` with ``bn_axis_name`` under a ``shard_map`` that
        checks varying manual axes, over the four chips: the kernel's sums
        are typed as varying as its map is, and the ``pmean`` leaves the
        statistics replicated (off the chip Pallas's interpreter cannot run
        under that check: ``tests/test_bn_moments.py``)."""
        from mercury_tpu.compat import shard_map
        from test_bn_moments import _blocks

        mesh = Mesh(np.array(v5e_devices), ("data",))
        block, _, x, variables = _blocks(64, False, jnp.bfloat16,
                                         axis_name="data")
        shaped = lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
        text = jax.jit(shard_map(
            lambda v, rows: block.apply(v, rows, mutable=["batch_stats"]),
            mesh=mesh, in_specs=(P(), P("data")), out_specs=(P("data"), P()),
            check_vma=True,
        )).lower(jax.tree.map(lambda a: shaped(a, P()), variables),
                 shaped(x, P("data"))).compile().as_text()
        assert text.count("tpu_custom_call") == 1 and "all-reduce" in text


# ---------------------------------------------------------------- fused step
def _compile_trainer_step(devices, world, **kw):
    """Compile the step ``Trainer`` would build — same model, optimizer,
    config and state layout — for ``world`` v5e devices. The trainer
    itself lives on the CPU mesh; only shapes and specs cross over."""
    from mercury_tpu import TrainConfig
    from mercury_tpu.train import Trainer
    from mercury_tpu.train.step import make_train_step

    fields = dict(
        model="resnet18", dataset="synthetic", world_size=world,
        batch_size=32, presample_batches=10, use_pallas=True,
        log_every=0, eval_every=0, heartbeat_every=0)
    fields.update(kw)
    config = TrainConfig(**fields)
    mesh = Mesh(np.array(devices[:world]), (config.mesh_axis,))
    # The trainer runs its model once on the CPU (the parameters' init):
    # the kernels turn into Mosaic calls only once it stands.
    with Trainer(config) as t, pytest.MonkeyPatch.context() as patch:
        patch.setattr(mercury_kernels, "on_tpu", lambda: True)
        scan = t.scan_steps
        step = make_train_step(t.model, t.tx, config, mesh, t.dataset.mean,
                               t.dataset.std, scan_steps=scan,
                               scoring_model=t.scoring_model,
                               image_shape=t._image_shape)
        ds = t.dataset
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=NamedSharding(mesh, x.sharding.spec)),
            (t.state, t._step_x, ds.y_train, ds.shard_indices))
        return step.lower(*args).compile()


@pytest.mark.slow
class TestFusedStepCompilesForV5e:
    def test_is_step_one_chip(self, v5e_devices, mosaic):
        compiled = _compile_trainer_step(v5e_devices, 1)
        # kernels 1 + 2 are inside the step
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.cost_analysis()["flops"] > 1e11

    def test_uniform_step_one_chip(self, v5e_devices, mosaic):
        _compile_trainer_step(v5e_devices, 1,
                              use_importance_sampling=False)

    def test_scan_chunk_one_chip(self, v5e_devices, mosaic):
        _compile_trainer_step(v5e_devices, 1, scan_steps=25,
                              checkpoint_every=0)

    def test_is_step_four_chips(self, v5e_devices, mosaic):
        _compile_trainer_step(v5e_devices, 4)

    def test_scoretable_step_one_chip(self, v5e_devices, mosaic):
        """L=5,000 / R=320: refused (17.16 MB of scoped VMEM) while the
        table was an ``[L, 1]`` column inside the fused step."""
        compiled = _compile_trainer_step(
            v5e_devices, 1, sampler="scoretable", refresh_size=320)
        assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------- pool ingest
def test_pool_ingest_dense_for_v5e(v5e_devices):
    """The pool's ingest at the benchmark cell's shapes (5,000-row resident
    set, 2,560 slots, 32x32x3 uint8): gather + crop/flip/normalize as the
    step runs them. With the channels minor the v5e compiler relaid the
    whole set out every step and moved 9.68 GB for a 31 MB pool (PERF.md
    section 6, PR 26); the selection pass on flat rows moves 0.75 GB."""
    import re

    from mercury_tpu.data.pipeline import augment_normalize

    n, pool, shape = 5000, 2560, (32, 32, 3)
    mean = np.asarray([0.5071, 0.4865, 0.4409], np.float32)
    std = np.asarray([0.2673, 0.2564, 0.2762], np.float32)

    def ingest(x_rows, gidx, key_data):
        with jax.named_scope("mercury_pool_ingest"):
            return augment_normalize(
                jax.random.wrap_key_data(key_data), x_rows[gidx],
                mean, std, image_shape=shape)

    compiled = _compiled(
        ingest, v5e_devices, ((n, int(np.prod(shape))), jnp.uint8),
        ((pool,), jnp.int32), ((2,), jnp.uint32))
    # no `copy` op whose result is the whole training set
    set_copies = [line for line in compiled.as_text().splitlines()
                  if re.search(rf"= u8\[{n},[^\]]*\]\S* copy\(", line)]
    assert not set_copies, set_copies
    accessed = compiled.cost_analysis()["bytes accessed"]
    assert accessed < 1.5e9, accessed


# ------------------------------------------------- the scoring forward's bytes
def _resnet50_shapes(devices, block_cls, rows, dtype=jnp.bfloat16):
    """A ResNet-50 (bf16 unless told) over ``rows`` CIFAR images, as shapes
    on one v5e device: ``(model, variables, images)``."""
    from mercury_tpu.models.resnet import ResNet

    model = ResNet(stage_sizes=[3, 4, 6, 3], block_cls=block_cls,
                   num_classes=100, compute_dtype=dtype)
    sh = NamedSharding(Mesh(np.array(devices[:1]), ("data",)), P())
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)),
                           train=True))
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        variables)
    images = jax.ShapeDtypeStruct((rows, 32, 32, 3), jnp.float32, sharding=sh)
    return model, variables, images


def _scoring_forward(devices, rows, dtype=jnp.bfloat16):
    """The scoring forward over a pool of ``rows`` (batch-statistic BN,
    nothing differentiates it), compiled for one v5e with the kernels as
    Mosaic calls: ``(executable, ENTRY instructions)``, each instruction as
    ``(name, result type, op, operand names, rest)``."""
    import re

    from mercury_tpu.models.resnet import Bottleneck

    model, variables, images = _resnet50_shapes(devices, Bottleneck, rows,
                                                dtype)
    with pytest.MonkeyPatch.context() as patch:   # as the ``mosaic`` fixture
        patch.setattr(mercury_kernels, "on_tpu", lambda: True)
        compiled = jax.jit(
            lambda v, x: model.apply(v, x, train=True,
                                     mutable=["batch_stats"])[0]
        ).lower(variables, images).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    instructions = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ([\w-]+)"
                     r"\((.*?)\)(.*)$", line)
        if m:
            name, result, op, operands, rest = m.groups()
            instructions.append(
                (name, result, op, re.findall(r"%[\w.\-]+", operands), rest))
    return compiled, instructions


@pytest.fixture(scope="module")
def pool_forward(v5e_devices):
    """The scoring forward at the benchmark cell's pool: 2,560 rows, bf16."""
    return _scoring_forward(v5e_devices, 2560)


def _maps_the_kernels_read(instructions, rows=2560, dtype="bf16"):
    """Sixteen closing units, sixteen Mosaic calls, each handed one ``conv2``
    raw map through one ``bitcast``: ``(instructions by name, the predicate
    for a conv2-sized map, the sixteen maps' names)``."""
    import re

    by_name = {name: (result, op, operands, rest)
               for name, result, op, operands, rest in instructions}
    calls = [name for name, (_, op, _, rest) in by_name.items()
             if op == "custom-call" and "tpu_custom_call" in rest]
    assert len(calls) == 16, calls
    is_map = re.compile(r"%s\[(%s)\]" % (dtype, "|".join(
        ",".join(map(str, (rows,) + shape[1:])) for shape in CONV2_MAPS))
    ).match
    raw = []
    for call in calls:
        view = by_name[call][2][0]
        assert by_name[view][1] == "bitcast", by_name[view]
        (source,) = by_name[view][2]
        assert is_map(by_name[source][0]), by_name[source]
        raw.append(source)
    assert len(set(raw)) == 16
    return by_name, is_map, raw


def test_pool_forward_writes_the_residual_maps_once_for_v5e(pool_forward):
    """With the statistic of each Bottleneck's closing BatchNorm taken from
    the convolution's output the v5e compiler counts 59.36 GB accessed, and
    the block's output is a ``kLoop`` fusion that reads the raw
    ``bf16[2560,32,32,256]`` map back (the two heaviest device ops of
    PERF.md section 5, PR 27); from the input's moments it is the
    convolution's own epilogue: 49.45 GB with the moments as two XLA passes
    (PR 30), 44.83 GB with them as one kernel call, whose operands the
    compiler does not count (2.31 GB: PERF.md section 6, PR 32)."""
    compiled, instructions = pool_forward
    accessed = compiled.cost_analysis()["bytes accessed"]
    assert accessed < 45.5e9, accessed
    # a bitcast moves no bytes; any other loop fusion that writes a stage-1
    # block output is the second pass PR 30 removed
    passes = [name for name, result, op, _, rest in instructions
              if result.startswith("bf16[2560,32,32,256]") and op == "fusion"
              and "kind=kLoop" in rest and "calls=%bitcast_fusion" not in rest]
    assert not passes, passes


def test_pool_forward_reads_each_conv2_map_twice_for_v5e(pool_forward):
    """Sixteen closing units, sixteen Mosaic calls, and each ``conv2`` raw
    map has two readers: the kernel, through a ``bitcast`` (the operand view
    is the layout the compiler already holds the map in: batch in the lanes
    for the 64-wide maps, channels in the lanes for the others), and the
    closing convolution, with BatchNorm_1's normalise + ReLU as its
    prologue. No activated copy of a map is written, none is relaid out, and
    no reduction passes over one outside a convolution's epilogue."""
    import re

    _, instructions = pool_forward
    # the sixteen maps the kernels read, each through one bitcast ...
    by_name, is_map, raw = _maps_the_kernels_read(instructions)
    # every ENTRY instruction whose result is one conv2-sized map is a
    # convolution fusion's output: nothing else writes one
    writers = {(op, re.search(r"kind=(\w+)", rest).group(1)
                if op == "fusion" else "")
               for _, (result, op, _, rest) in by_name.items()
               if is_map(result) and op != "get-tuple-element"}
    assert writers <= {("fusion", "kOutput")}, writers
    # ... and their other reader: the closing convolution, and only it
    for source in raw:
        readers = sorted(
            (op, rest) for _, (_, op, operands, rest) in by_name.items()
            if source in operands)
        assert [op for op, _ in readers] == ["bitcast", "fusion"], readers
        assert ("kind=kOutput" in readers[1][1]
                and "conv_general_dilated" in readers[1][1]), readers[1]


@pytest.mark.parametrize("rows,dtype", [(320, jnp.bfloat16),
                                        (2560, jnp.float32)],
                         ids=["pool-of-320", "f32"])
def test_scoring_forward_hands_over_bitcasts_at_other_shapes_for_v5e(
        v5e_devices, rows, dtype):
    """The kernel is every non-differentiated ``Bottleneck`` forward's, not
    the cell's alone: at the reference pool (320 rows: 320 lanes are no
    multiple of 128) and at f32 the whole forward compiles for the v5e too,
    and the compiler still holds each ``conv2`` map in the order the kernel
    takes it in, so no map is relaid out on the way."""
    _, instructions = _scoring_forward(v5e_devices, rows, dtype)
    _maps_the_kernels_read(
        instructions, rows, {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype])


def test_differentiated_pass_is_the_plain_forms_for_v5e(v5e_devices):
    """Under ``jax.grad`` the closing unit is the plain form (``nn.Conv``
    then ``nn.BatchNorm``): the train forward and backward at the cell's
    batch (256 rows) cost the v5e compiler what the parent's block costs
    it, to the FLOP and the byte."""
    from mercury_tpu.models.resnet import Bottleneck
    from test_bn_moments import PlainBottleneck

    def cost(block_cls):
        model, variables, images = _resnet50_shapes(v5e_devices, block_cls,
                                                    256)

        def loss(params, batch_stats, x):
            logits, new = model.apply(
                {"params": params, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            return jnp.sum(jnp.square(logits)), new

        compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
            variables["params"], variables["batch_stats"], images).compile()
        analysis = compiled.cost_analysis()
        return (analysis["flops"], analysis["bytes accessed"],
                compiled.memory_analysis().temp_size_in_bytes)

    assert cost(Bottleneck) == cost(PlainBottleneck)


# ----------------- the next-token cells (``st21b-is-8k``, ``kn2-is-8k``)
#: What the v5e's compiler allows one program (it refused PR 35's whole-pool
#: reference with "Used 19.73G of 15.75G hbm").
HBM_LIMIT = int(15.75 * 2 ** 30)
#: Each with the bytes of its state: parameters x 12 B (parameters, mu, nu).
TOKEN_CELLS = {"st21b-is-8k": 370_547_200 * 12, "kn2-is-8k": 330_589_184 * 12}


@pytest.mark.parametrize("cell", sorted(TOKEN_CELLS))
def test_token_cell_step_fits_one_v5e(v5e_devices, cell):
    """The cell's own step (the pool of 10 sequences of 8,192 tokens scored
    a row at a time, one trained on) at the published widths: the
    attention is splash-attention's Mosaic kernels (grouped-query heads of
    128; latent attention's of 192 against 128), and the compiler's peak
    (state, bfloat16 weights, one row's activations and logits) lies under
    the chip's limit. The scoring pass's head is the kernel over vocabulary
    blocks (``mercury_head_nll`` under ``mercury_scoring``) and the only
    whole ``[8192, V]`` float32 logits are the train pass's.
    ``st21b-is-8k``'s operands of the attention come from
    ``mercury_rope_heads`` (PR 47), three a layer: what the plain forms left
    in a row's body, the rotation's halves ``f32[8192,28,64]`` (their
    copies, a ``negate`` of its own), is gone; ``kn2-is-8k``'s latent heads
    (128 + 64) keep the plain forms. Three quarters of a minute to a minute
    and a half each."""
    import re

    from perfbench.cell import Cell

    fields = Cell(cell).train_config_fields(seed=7, trace=False)
    compiled = _compile_trainer_step(v5e_devices, 1, **fields)
    text = compiled.as_text()
    assert "splash_mqa" in text and "mercury_score_draw_kernel" in text
    vocab = fields["num_classes"]
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "mercury_head_nll" in line]
    assert kernels and all("mercury_scoring" in line
                           and "mercury_train" not in line
                           for line in kernels)
    logits = [line for line in text.splitlines()
              if f"f32[8192,{vocab}]" in line and "op_name" in line]
    assert logits and all("mercury_train" in line
                          and "mercury_scoring" not in line
                          for line in logits)
    operands = [line for line in text.splitlines()
                if re.match(r"\s*%mercury_rope_heads\S* = ", line)
                and "tpu_custom_call" in line]
    if cell == "st21b-is-8k":
        assert operands and all("mercury_attention" in line
                                for line in operands)
        # an instruction of the program or of a loop's body, not one inside
        # a fusion: "%name = f32[8192,28,64]{...} copy(" and the like
        halves = [line for line in text.splitlines()
                  if re.match(r"\s*(ROOT )?%\S+ = f32\[8192,28,64\]\S* "
                              r"(negate|copy)\(", line)
                  and "fused_computation" not in line]
        assert not halves, halves[:3]
    else:
        assert not operands
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 0.99 * TOKEN_CELLS[cell]
    assert memory.peak_memory_in_bytes < HBM_LIMIT, memory


def _reference_block(devices, cell, program, quantize=None):
    """One of the two programs the plain reference's training side runs a
    row at a time for the token cell (``perfbench/reference.py``:
    ``score_pool``'s ``score`` and ``make_loss_and_grad``'s ``add_block``
    at ``check.train_block_rows`` rows), compiled for one v5e at the
    configuration's own shapes."""
    from mercury_tpu.models import create_model
    from perfbench import reference
    from perfbench.cell import Cell

    cell = Cell(cell)
    arch, fields = cell.config["reference"], cell.config["train_config"]
    rows = int(cell.config["check"]["train_block_rows"])
    fam = reference.family(arch)
    model = create_model(fields["model"], num_classes=fields["num_classes"],
                         cut=tuple(fields["model_cut"]))
    sh = NamedSharding(Mesh(np.array(devices[:1]), ("data",)), P())

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = jax.tree.map(
        lambda a: shaped(a.shape, a.dtype), jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"])
    tokens = shaped((rows, fields["seq_len"]), jnp.int32)

    def weighted(p, x, y, scaled_probs):
        return fam.example_loss(
            fam.forward(p, None, x, arch, quantize), y) / scaled_probs

    if program == "score":
        return jax.jit(lambda p, x, y: weighted(p, x, y, 1.0)).lower(
            params, tokens, tokens).compile()

    def add_block(so_far, p, *block):
        part = jax.value_and_grad(
            lambda *a: jnp.sum(weighted(*a)))(p, *block)
        return jax.tree.map(jnp.add, so_far, part)

    return jax.jit(add_block, donate_argnums=0).lower(
        (shaped((), jnp.float32), params), params, tokens, tokens,
        shaped((rows,), jnp.float32)).compile()


@pytest.mark.parametrize("program", ["score", "grad"])
@pytest.mark.parametrize("cell", sorted(TOKEN_CELLS))
def test_token_cell_reference_blocks_fit_one_v5e(v5e_devices, cell, program):
    """The float32 reference of one row of 8,192 tokens, scored (20 s to
    compile) and differentiated into the gradient sum (50 s): blocked
    attention and a checkpoint a layer keep the peak well under the chip's
    limit, parameters and the gradient sum included, beside nothing else
    (the trainer is closed by then)."""
    memory = _reference_block(v5e_devices, cell, program).memory_analysis()
    assert memory.peak_memory_in_bytes < HBM_LIMIT, memory
    assert memory.temp_size_in_bytes < 8 * 2 ** 30, memory
