"""Attribution of the compiled step's operations to the program's layers."""
from bench.lib import hlo

TEXT = '''HloModule jit_step_fn_packed, is_scheduled=true

FileNames
1 "/ckpt/src/repro/core/geomed.py"
2 "/ckpt/src/repro/core/saga.py"
3 "/ckpt/src/repro/models/mamba2.py"
4 "/ckpt/bench/lib/harness.py"

FunctionNames
1 "weiszfeld_pytree"

FileLocations
1 {file_name_id=1 function_name_id=1 line=190 end_line=190 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=150 end_line=150 column=1 end_column=2}
3 {file_name_id=3 function_name_id=1 line=170 end_line=170 column=1 end_column=2}
4 {file_name_id=4 function_name_id=1 line=10 end_line=10 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}
4 {file_location_id=4 parent_frame_id=1}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/mul" stack_frame_id=1}
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %add.1 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/add" stack_frame_id=2}
  ROOT %multiply.2 = f32[4]{0} multiply(%add.1, %add.1), metadata={op_name="jit(f)/mul" stack_frame_id=3}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/mul" stack_frame_id=1}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.3 = f32[4]{0} copy(%fusion.2)
  %negate.4 = f32[4]{0} negate(%copy.3), metadata={op_name="jit(f)/neg" stack_frame_id=4}
  ROOT %tuple.5 = (f32[4]{0}) tuple(%negate.4)
}
'''


def test_ops_map_to_the_layer_of_their_frames():
    phases = hlo.op_phases(TEXT)
    assert hlo.module_name(TEXT) == "jit_step_fn_packed"
    assert phases["fusion.1"] == "aggregate"      # geomed.py, itself and fused
    assert phases["fusion.2"] == "mixed"          # saga.py + mamba2.py fused
    assert phases["copy.3"] == "unattributed"     # no metadata: not guessed
    assert phases["negate.4"] == "other"          # outside the program
    assert phases["add.1"] == "vr"
    assert phases["multiply.2"] == "grads"


def test_old_style_source_file_metadata():
    text = ('HloModule m\n\nENTRY %main (a: f32[]) -> f32[] {\n'
            '  ROOT %exp.1 = f32[] exponential(%a), metadata={op_name="x" '
            'source_file="/x/src/repro/optim/optimizers.py" source_line=3}\n}\n')
    assert hlo.op_phases(text) == {"exp.1": "optimizer"}
